"""Continuous-batching decode engine over fixed slots.

The TPU serving problem (PAPERS.md #1/#5 regime): requests arrive at
arbitrary times with arbitrary prompt/output lengths, but XLA wants
ONE compiled program per shape. The resolution is the standard
continuous-batching design (Orca/vLLM lineage) restricted to fully
static shapes:

- the engine owns S decode **slots** — lanes of one SlotCache
  (models/generate.py) sized [depth, S, total_len, H_kv, Dh] at
  startup, never reshaped;
- every engine step advances ALL S lanes by one token AND samples
  each lane's next token on device (``slot_decode_sample_step`` — one
  compiled program, mixed-age batch, fused sampling);
- prompts are ingested by **chunked prefill** (Sarathi-style):
  ``prefill_chunk`` writes one power-of-two-bucketed chunk straight
  into the freed lane of the donated cache, co-scheduled with decode
  steps under a per-step token budget (serve/scheduler.plan_chunks),
  so running lanes never stall behind a long prompt;
- therefore the engine compiles a BOUNDED program set — one decode
  program plus one chunk program per bucket width — enumerable at
  ``warmup()``, after which a varied request mix (staggered arrivals,
  different lengths, evictions) triggers **zero further compilation**
  (pinned by tests/test_serve.py via ``compile_counts``).

The decode loop is **device-resident**: the only steady-state
device→host transfer is the [S] int32 token vector of the PREVIOUS
step, fetched after the current step's work has been dispatched
(dispatch step i+1, then retire step i's tokens while the device
computes) — no full-logits round-trip, no per-slot Python sampling
(both pinned by tests). The cache is donated through every program
(train/fast.py's convention) so XLA keeps one KV buffer; the token
vector deliberately is NOT donated — the host still owes a read of
the previous step's values.

Scheduling policy lives in serve/scheduler.py (admission, FIFO,
deadlines, chunk planning); this module is the data plane plus
per-request bookkeeping. Observability flows through
utils/metrics.MetricsWriter: ``serve_step`` records (queue depth,
slot occupancy, evictions, chunk tokens, dispatch/retire split) and
``serve_request`` records (status, TTFT, decode tokens/s) land in the
same JSONL stream the trainer writes; span tracing emits
``serve.prefill_chunk`` / ``serve.decode`` / ``serve.sample``.

Sampling: greedy (temperature 0, the correctness-pinned path — token-
identical to models/generate.generate) or seeded temperature/top-p
sampling fused into the jitted step via per-slot ``jax.random`` keys
(ALSO token-identical to a seeded ``generate()`` — same fold_in
stream, pinned by tests). ``top_k`` stays generate-only: its k is a
compiled shape, so per-request values would recompile per mix.
"""

from __future__ import annotations

import time

_IMPORT_T0 = time.perf_counter()  # → ``startup.import``, at the last line

import importlib
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

# Aliased: ``prefill_chunk`` is also an engine CONFIG name (the chunk
# width __init__ parameter), which would shadow the function inside
# closures defined there.
from ddp_tpu.models.generate import (
    init_paged_slot_cache,
    init_slot_cache,
)
from ddp_tpu.models.generate import prefill_chunk as _gpt2_chunk
from ddp_tpu.models.generate import (
    slot_decode_sample_step as _gpt2_decode,
)
from ddp_tpu.models.generate import slot_decode_step as _decode_step
from ddp_tpu.models.generate import slot_verify_step as _verify_step
from ddp_tpu.models import sdar as _sdar
from ddp_tpu.models.lm import LMSpec, head_dim_of
from ddp_tpu.ops.decode import DEFAULT_BLOCK_K, decode_block
from ddp_tpu.obs.tracer import Tracer, get_tracer, imported
from ddp_tpu.serve.pages import PrefixCache, page_demand
from ddp_tpu.serve.scheduler import (
    Admission,
    Request,
    Scheduler,
    next_pow2,
    prev_pow2,
)
from ddp_tpu.utils.metrics import MetricsWriter, StatSummary


def _abstract(args):
    """Shapes and dtypes of a call's arguments (the arrays themselves
    may be donated by it)."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), args)


def _named_fn(name, fn):
    """``fn`` under ``name``: what the profiler's trace and the compile
    records call its program (``jit_<name>``)."""
    fn.__name__ = fn.__qualname__ = name
    return fn

# The blocks that bring their own one-token programs, by the module
# that holds each (imported where a spec asks for it, so that no other
# model's start-up pays). What the engine asks of such a module:
# ``validate(spec)``; ``prefill_chunk`` and ``slot_decode_sample_step``
# under the GPT-2 path's signatures; ``RECURRENT``, whether a lane
# holds state that a step it was not owed would spoil, so that a decode
# step advances only the lanes ``cache.live`` names; and, where a lane
# holds K/V of more than one kind, ``attended_rows(spec, rows)``, what
# a decode step reads of each; where a lane's keys are selected,
# ``dsa_rows(spec, first, count)``, the rows queries at those positions
# score and attend, ``lane_dtype(params)``, what a lane's rows are
# stored in, a lane whose cache carries ``sel`` (models/generate.
# SlotCache), and ONE MORE output of both programs, the call's expert
# pairs (routed, held here), fetched a step behind like the tokens. The
# step loop holds three jitted callables and no model's name.
_BLOCK_MODULES = {
    "granite_hybrid": "ddp_tpu.models.granite_hybrid",
    "sambay": "ddp_tpu.models.sambay",
    "glm_dsa": "ddp_tpu.models.glm_dsa",
}


def block_module(spec: LMSpec):
    """The module of ``spec.block``'s one-token programs; None for the
    blocks the engine holds itself (``gpt2``, and ``qwen3_moe`` by
    blocks)."""
    name = _BLOCK_MODULES.get(spec.block)
    return importlib.import_module(name) if name else None


# Why each knob below has no meaning yet for a recurrent lane: its
# history is a state, not rows that can be paged, shared, rolled back
# or re-quantized.
RECURRENT_REFUSALS = {
    "page_size": "pages and the radix prefix cache share K/V rows "
    "between lanes; a recurrent state at a page boundary would have to "
    "be snapshotted to be shared, and is not",
    "kv_dtype": "int8 rows are quantized once on write; a state is "
    "rewritten every step and stays float32",
    "spec_tokens": "a rejected draft rolls the cache back by moving "
    "pos; a state that has absorbed the drafts cannot be rolled back",
    "export_prefix": "a prefix's K/V pages can be shipped, the "
    "recurrent state that goes with them is not kept per page",
    "install_prefix": "a frame of K/V pages carries no recurrent "
    "state to resume from",
}
# And for a lane of latent rows whose keys an indexer selects: it holds
# no K/V rows at all, and its decode reads rows by index.
LATENT_REFUSALS = {
    "page_size": "a lane holds one latent and one indexer row a "
    "position, not K/V rows; the selection gathers rows of a lane by "
    "index and no page table stands between",
    "kv_dtype": "the latent and indexer rows are stored as the weights "
    "are; no int8 form of a latent row is defined",
    "spec_tokens": "a verify round would have to select for several "
    "positions of a lane in one step, and the decode selects for one",
    "export_prefix": "a prefix here is latent and indexer rows, which "
    "the page wire format does not carry",
    "install_prefix": "a frame of K/V pages holds nothing a latent "
    "lane could resume from",
}

# Completion statuses.
COMPLETE = "complete"
TIMEOUT_EVICTED = "timeout_evicted"  # deadline hit while decoding
TIMEOUT_QUEUE = "timeout_queue"  # deadline hit while queued
REJECTED_TOO_LONG = "rejected_too_long"  # slipped past the front door


@dataclass
class Completion:
    """One finished request: everything the frontend returns.

    ``ttft`` is None for requests that never produced a token (queue
    timeouts, mid-prefill evictions, refill-time rejections) — they
    must not pollute the TTFT summaries with queue-wait times.
    """

    rid: int
    status: str
    prompt: list[int]
    tokens: list[int]
    ttft: Optional[float]  # seconds, submit → first token observed
    decode_seconds: float  # first token → finish
    submitted: float
    finished: float
    # Speculative decoding only: fraction of draft proposals the
    # target accepted over this request's verify rounds (None on the
    # non-speculative path, or before any round ran).
    spec_acceptance: Optional[float] = None
    # Seconds queued before a lane bound the request (None for
    # requests evicted from the queue — they never bound).
    queue_s: Optional[float] = None
    # Request-trace digest (obs/reqtrace.py): trace id + queue/
    # prefill/decode split + spec stats. None with tracing off.
    trace: Optional[dict] = None
    # Paged-KV prefix reuse only (PR 12): prompt tokens served from
    # cached prefix pages — zero prefill compute paid for them. None
    # on fixed-lane engines; 0 = paged but missed.
    prefix_hit_tokens: Optional[int] = None
    # Tokens that arrived AT ``ttft``: 1 on the one-token path; under
    # block diffusion the first committed block's share of the answer.
    first_tokens: int = 1
    # Block diffusion, on request (``record_blocks``): every forward
    # the lane ran for this request, ``(pos, tokens [B], mask [B])`` as
    # the forward saw them — what the benchmark hands its reference.
    block_inputs: Optional[list] = None
    # Keys selected before they are attended, on request
    # (``record_selection``): the rows each layer selected for the
    # step that produced the LAST token (``[layers][K]``, -1 past a
    # young lane's rows; its query stood at position ``len(prompt) +
    # len(tokens) - 2``). None where no decode step ran.
    selected_rows: Optional[list] = None

    @property
    def decode_tokens_per_s(self) -> float:
        n = len(self.tokens) - self.first_tokens  # tokens after the first
        return n / self.decode_seconds if self.decode_seconds > 0 else 0.0

    @property
    def tpot_s(self) -> Optional[float]:
        """Time per output token (decode only) — the per-request SLI
        behind the tpot_p50 objective; None before a second token.
        Committed tokens, never forward positions."""
        n = len(self.tokens) - self.first_tokens
        if n <= 0 or self.decode_seconds <= 0:
            return None
        return self.decode_seconds / n


@dataclass
class _Slot:
    """Host-side bookkeeping for one lane."""

    # Lane index, fixed at engine construction. Identity, not a
    # field-equality lookup: two freshly-reset slots compare equal
    # under the generated __eq__, so list.index() would be wrong.
    index: int = -1
    request: Optional[Request] = None
    tokens: list[int] = field(default_factory=list)
    # Tokens SCHEDULED on device for this request, including ones whose
    # values the host has not fetched yet (len(tokens) lags by the
    # in-flight step). Retirement decisions use this — counts are
    # host-known at dispatch time, values are not.
    emitted: int = 0
    prefill_pos: int = 0  # prompt tokens ingested so far
    first_token_at: Optional[float] = None  # None = no token observed
    queue_s: Optional[float] = None  # submit → lane bind wait
    # Speculative-decoding tallies for this occupancy (host-side —
    # the verify round's matched counts are fetched anyway).
    spec_drafted: int = 0
    spec_accepted: int = 0
    # Paged mode only: the page ids this lane's table maps (prefix +
    # private, in position order) and how many leading prompt tokens
    # came from cached prefix pages.
    pages: list[int] = field(default_factory=list)
    matched_tokens: int = 0
    # Block diffusion only. ``prefill_target``: the prompt tokens the
    # chunks ingest (its whole blocks; the rest opens the first
    # generated block), None on the one-token path. ``installed``: the
    # final chunk has put the lane's first block on the device.
    # ``lead``: prompt tokens at the head of the block in hand.
    # ``block_pos``: that block's first position. ``first_tokens``:
    # tokens the first commit brought. ``block_log``: the recorded
    # forwards, when the request asked for them.
    prefill_target: Optional[int] = None
    installed: bool = True
    lead: int = 0
    block_pos: int = 0
    first_tokens: int = 1
    block_log: Optional[list] = None
    # A lane whose keys are selected, asked with ``record_selection``:
    # what the step that produced the request's last token selected
    # (``[layers, K]`` on the device until the request finishes).
    selection: Any = None

    @property
    def free(self) -> bool:
        return self.request is None

    @property
    def prefill_goal(self) -> int:
        if self.prefill_target is not None:
            return self.prefill_target
        return len(self.request.prompt)

    @property
    def prefilling(self) -> bool:
        return self.request is not None and (
            self.prefill_pos < self.prefill_goal or not self.installed
        )

    @property
    def decoding(self) -> bool:
        return self.request is not None and not self.prefilling


def drain_eta_s(
    retire_times: list[float], depth: int
) -> Optional[float]:
    """Seconds until ``depth`` queued requests drain at the measured
    retirement rate, from a window of retire clock times.

    The backpressure Retry-After derivation (pure — unit-testable
    with synthetic clocks): (count-1) retirements over the window's
    span give requests/second; depth over that rate is the ETA. None
    when the window can't support a rate (fewer than two retires, or
    a same-instant burst) — callers fall back to a static hint. An
    empty queue still returns a positive beat (one retirement
    period): the 429 raced a retire, and "retry immediately" is how
    thundering herds start.
    """
    if len(retire_times) < 2:
        return None
    span = retire_times[-1] - retire_times[0]
    if span <= 0:
        return None
    rate = (len(retire_times) - 1) / span
    return max(1, depth) / rate


def resolve_engine_knobs(
    spec: LMSpec,
    *,
    slots: int = 4,
    prefill_len: Optional[int] = None,
    prefill_chunk: Optional[int] = None,
    min_bucket: Optional[int] = None,
    step_token_budget: Optional[int] = None,
    decode_attn: str = "auto",
    kv_dtype: str = "fp32",
    page_size: int = 0,
    kv_pages: Optional[int] = None,
    spec_tokens: int = 0,
    draft_spec: Optional[LMSpec] = None,
    has_draft_params: bool = False,
) -> dict:
    """Validate + resolve the engine's knob surface — the SINGLE rule
    set for what configurations are constructible.

    ``ServeEngine.__init__`` consumes this verbatim. Raises
    the same ``ValueError`` messages the engine always raised; returns
    the resolved values (defaults filled, pow2 snapping and caps
    applied) the engine assigns.
    """
    if slots < 1:
        raise ValueError(f"slots must be >= 1, got {slots}")
    prefill_len = prefill_len or max(1, spec.total_len // 2)
    if not 0 < prefill_len <= spec.total_len - 1:
        raise ValueError(
            f"prefill_len {prefill_len} must leave room to decode "
            f"inside total_len {spec.total_len}"
        )
    # Decode-attention impl (ops/decode.py): resolved ONCE, like
    # best_attention — the flash-decode Pallas kernel on TPU, the
    # bit-identical jnp reference elsewhere; "flash" forces the
    # kernel (interpret mode off-TPU: how CPU tests pin token
    # identity).
    if decode_attn not in ("auto", "flash", "reference"):
        raise ValueError(
            f"decode_attn must be auto|flash|reference, got "
            f"{decode_attn!r}"
        )
    if decode_attn == "auto":
        decode_attn = (
            "flash" if jax.default_backend() == "tpu" else "reference"
        )
    if kv_dtype not in ("fp32", "int8"):
        raise ValueError(
            f"kv_dtype must be fp32|int8, got {kv_dtype!r}"
        )
    # How the resolved impl runs here — chosen from the platform, so
    # reported (startup JSON), never left to inference.
    decode_kernel = "xla"
    decode_block_k = None
    if decode_attn == "flash":
        from ddp_tpu.ops.flash import pallas_kernel_mode

        decode_kernel = pallas_kernel_mode()
        # The kernel's effective KV block, resolved here so a lane
        # length with no tile-aligned block fails at construction with
        # the shape named (ops/decode.decode_block), not inside Mosaic.
        try:
            decode_block_k = decode_block(
                spec.total_len,
                spec.num_kv_heads or spec.num_heads,
                head_dim_of(spec),
                jnp.int8 if kv_dtype == "int8" else jnp.float32,
            )
        except ValueError as e:
            raise ValueError(
                f"decode_attn=flash cannot tile total_len "
                f"{spec.total_len} with a {kv_dtype} cache ({e}); use "
                "decode_attn=reference"
            ) from e
    if kv_pages is not None and not page_size:
        raise ValueError(
            "--kv_pages needs --page_size (the page pool only "
            "exists in paged mode)"
        )
    paged = bool(page_size)
    page_size = int(page_size)
    lane_pages = resolved_kv_pages = None
    if paged:
        if page_size < 1 or (page_size & (page_size - 1)):
            raise ValueError(
                f"--page_size must be a power of two, got "
                f"{page_size}"
            )
        if spec.total_len % page_size:
            raise ValueError(
                f"--page_size {page_size} must divide the model's "
                f"total_len {spec.total_len}: a partial tail page "
                "would break the page-granular tail-chunk "
                "invariant (every chunk write maps through whole "
                "pages)"
            )
        lane_pages = spec.total_len // page_size
        resolved_kv_pages = int(
            kv_pages
            if kv_pages is not None
            # Capacity-neutral default: the pool holds exactly the
            # fixed-lane layout's lines (+ the scratch page), so
            # any sharing is pure headroom.
            else slots * lane_pages + 1
        )
        if resolved_kv_pages < lane_pages + 1:
            raise ValueError(
                f"--kv_pages {resolved_kv_pages} cannot hold one "
                f"full-context lane: needs >= total_len/"
                f"--page_size + 1 scratch = {lane_pages + 1}"
                " (a maximal request could never bind — permanent "
                "queue head starvation)"
            )
    if spec_tokens:
        if spec_tokens < 1:
            raise ValueError(
                f"spec_tokens must be >= 1, got {spec_tokens}"
            )
        if draft_spec is None or not has_draft_params:
            raise ValueError(
                "speculative decoding needs draft_spec AND "
                "draft_params alongside spec_tokens"
            )
        if draft_spec.vocab_size != spec.vocab_size:
            raise ValueError(
                f"draft vocab {draft_spec.vocab_size} != target "
                f"vocab {spec.vocab_size}"
            )
        if draft_spec.total_len != spec.total_len:
            raise ValueError(
                f"draft total_len {draft_spec.total_len} != target "
                f"total_len {spec.total_len} (the caches track the "
                "same positions)"
            )
        if spec_tokens >= spec.total_len - prefill_len:
            raise ValueError(
                f"spec_tokens {spec_tokens} leaves no decode room "
                f"past prefill_len {prefill_len} in total_len "
                f"{spec.total_len}"
            )
    spec_tokens = int(spec_tokens)
    # How the model generates is its spec's to say, no flag's. Block
    # diffusion (models/sdar.py): a lane holds a block of ``block_len``
    # positions and a step is one forward over it; a block that
    # generates by blocks needs a block length. A block with a module
    # of its own (``block_module``) needs none: it decodes one token a
    # step like ``gpt2``, with recurrent state beside its K/V rows.
    block_len = int(spec.block_length)
    module = block_module(spec)
    recurrent = bool(module and module.RECURRENT)
    if module:
        module.validate(spec)
    refusals = (RECURRENT_REFUSALS if recurrent
                else LATENT_REFUSALS if spec.kv_lora_rank else None)
    if refusals:
        for knob, given in (
            ("page_size", paged), ("kv_dtype", kv_dtype != "fp32"),
            ("spec_tokens", spec_tokens),
        ):
            if given:
                raise ValueError(
                    f"{knob} does not apply to the {spec.block} block: "
                    f"{refusals[knob]}"
                )
    elif module is None and (block_len or spec.block != "gpt2"):
        _sdar.validate(spec)
        if paged or kv_dtype != "fp32" or spec_tokens:
            raise ValueError(
                "a model that generates by blocks serves from fixed "
                "fp32 lanes without speculation: page_size, "
                f"kv_dtype={kv_dtype!r} and spec_tokens do not apply"
            )
    # Admission context ceiling: the verify round's K-1 reserve (the
    # block step's B-1: a last block may overhang what the request
    # asked for) comes off the budget check, never the cache geometry.
    ctx_len = spec.total_len - max(0, spec_tokens - 1, block_len - 1)
    # Decode-path tokens dispatched per running lane per step: 1
    # plain, K under speculation (the verify round processes K
    # positions per lane — plan_chunks accounts them all), B in a
    # block.
    tokens_per_decode = max(1, spec_tokens, block_len)
    chunk = next_pow2(
        prefill_chunk
        if prefill_chunk
        else min(next_pow2(prefill_len), 64)
    )
    # A chunk's write region [start, start + width) must fit the
    # cache at start = 0 — cap at the largest pow2 <= total_len.
    chunk = min(chunk, prev_pow2(spec.total_len))
    # The smallest bucket must fit the cache at ANY admissible
    # start (max start = prefill_len - 1, so the space floor is
    # total_len - prefill_len + 1 >= 2): a wider bucket's pad
    # overhang would make dynamic_update_slice clamp the write
    # start and silently shift the chunk over live cache lines.
    min_bucket = min(
        chunk,
        next_pow2(min_bucket) if min_bucket else min(8, chunk),
        prev_pow2(spec.total_len - prefill_len + 1),
    )
    if min_bucket < block_len:
        raise ValueError(
            f"min_bucket {min_bucket} is narrower than the model's "
            f"block of {block_len}: chunks hold whole blocks"
        )
    step_token_budget = (
        step_token_budget
        if step_token_budget
        else chunk + slots * tokens_per_decode
    )
    if step_token_budget < min_bucket + slots * tokens_per_decode:
        # Below this floor the prefill head can starve forever
        # while lanes decode (the budget never fits even the
        # smallest bucket after decode tokens are accounted).
        raise ValueError(
            f"step_token_budget {step_token_budget} cannot "
            f"sustain prefill progress: needs >= min_bucket "
            f"({min_bucket}) + slots ({slots}) x decode tokens "
            f"per lane ({tokens_per_decode})"
        )
    return {
        "slots": slots,
        "prefill_len": prefill_len,
        "decode_attn": decode_attn,
        "decode_kernel": decode_kernel,
        "decode_block_k": decode_block_k,
        "kv_dtype": kv_dtype,
        "paged": paged,
        "page_size": page_size,
        "lane_pages": lane_pages,
        "kv_pages": resolved_kv_pages,
        "spec_tokens": spec_tokens,
        "block_len": block_len,
        "recurrent": recurrent,
        "ctx_len": ctx_len,
        "tokens_per_decode": tokens_per_decode,
        "chunk": chunk,
        "min_bucket": min_bucket,
        "step_token_budget": step_token_budget,
    }


class ServeEngine:
    """Fixed-slot continuous-batching engine for one causal LM.

    ``slots`` fixes the decode batch shape; ``prefill_len`` is the
    admission ceiling for prompts (default half the position table).
    ``prefill_chunk`` (power of two, default min(pow2(prefill_len),
    64)) is the full chunk width prompts are ingested at;
    ``min_bucket`` floors the power-of-two bucket of the final partial
    chunk; ``step_token_budget`` bounds chunk-plus-decode tokens
    dispatched per step (default chunk + slots — one full-width chunk
    can ride along with a full decode batch). ``admit_every`` N > 0
    spaces admissions: while any lane runs, at most one request is
    bound to a lane every N engine steps (0, the default: every free
    lane is refilled at once). Requests of one length that are admitted
    together finish together and are refilled together, for ever: the
    engine then alternates between steps full of prefill chunks and
    steps of decode alone, and answers come ``slots`` at a time. With
    N a little under (steps a request takes) / ``slots`` the lanes
    spread over a request's length while the engine fills; from then
    on each lane frees N or more steps after the last and is refilled
    at once, so the spacing costs nothing. A larger N starves lanes.
    ``clock`` is injectable for deterministic tests; MetricsWriter
    ``metrics`` may be shared with a trainer's stream or omitted.

    ``page_size`` > 0 (power of two dividing ``spec.total_len``)
    switches the KV cache to the PAGED layout (PR 12): K/V live in a
    pool of ``kv_pages`` pages (default slots · total_len/page_size +
    1 — capacity-neutral vs fixed lanes, plus the scratch page) and
    each lane maps pages through an int32 table, so prompts sharing a
    prefix prefill it ONCE and fork the pages copy-free
    (serve/pages.PrefixCache: radix index, refcounts, LRU eviction of
    cached prefixes). Admission then accounts in free pages instead
    of lanes × ctx_len; outputs stay token-identical to the
    fixed-lane engine (pinned by tests/test_paged.py). Speculative
    decoding composes: the γ-1 write reserve is accounted in pages,
    while the DRAFT cache stays fixed-lane (it is lane-private —
    nothing to share); a prefix hit skips TARGET prefill for the
    matched tokens, which can only lower draft acceptance there,
    never correctness (verify guarantees the stream).
    """

    def __init__(
        self,
        spec: LMSpec,
        params: Any,
        *,
        slots: int = 4,
        prefill_len: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        min_bucket: Optional[int] = None,
        step_token_budget: Optional[int] = None,
        admit_every: int = 0,
        max_queue: int = 64,
        metrics: Optional[MetricsWriter] = None,
        tracer: Optional[Tracer] = None,
        clock: Callable[[], float] = time.monotonic,
        sanitize: bool = False,
        xprof=None,
        decode_attn: str = "auto",
        kv_dtype: str = "fp32",
        page_size: int = 0,
        kv_pages: Optional[int] = None,
        draft_spec: Optional[LMSpec] = None,
        draft_params: Any = None,
        spec_tokens: int = 0,
        reqtrace: bool = False,
        reqtrace_keep: int = 512,
        trace_seed: Optional[int] = None,
        slo=None,
        recorder=None,
        model_version: Optional[str] = None,
    ):
        t_init = time.perf_counter()  # ``startup.state``, kept at the end
        # The whole knob surface validates + resolves through the
        # module-level resolver.
        knobs = resolve_engine_knobs(
            spec,
            slots=slots,
            prefill_len=prefill_len,
            prefill_chunk=prefill_chunk,
            min_bucket=min_bucket,
            step_token_budget=step_token_budget,
            decode_attn=decode_attn,
            kv_dtype=kv_dtype,
            page_size=page_size,
            kv_pages=kv_pages,
            spec_tokens=spec_tokens,
            draft_spec=draft_spec,
            has_draft_params=draft_params is not None,
        )
        prefill_len = knobs["prefill_len"]
        self.decode_attn = knobs["decode_attn"]
        self.decode_kernel = knobs["decode_kernel"]
        self.kv_dtype = knobs["kv_dtype"]
        # Paged KV + radix prefix reuse (PR 12, serve/pages.py):
        # --page_size > 0 flips the cache to the page-pool layout
        # (PagedSlotCache) and admission to free-page accounting.
        # 0 (the default) is the fixed-lane control — byte-identical
        # transfer shapes, compile counts and /metricsz exposition to
        # the pre-paging engine.
        self.paged = knobs["paged"]
        self.page_size = knobs["page_size"]
        if self.paged:
            self._lane_pages = knobs["lane_pages"]
            self.kv_pages = knobs["kv_pages"]
        # Speculative decoding: a draft LM proposes spec_tokens greedy
        # continuations per lane; the target verifies them in ONE
        # batched step (models/generate.slot_verify_step). The verify
        # round writes K = spec_tokens rows per lane, so admission
        # reserves K-1 cache lines (a lane one round short of budget
        # may overshoot its context by up to K-2 positions — reserved
        # rather than clamp-shifted over live lines).
        self.spec_tokens = knobs["spec_tokens"]
        # Block diffusion (models/sdar.py), read from the model's spec:
        # > 0, a decoding lane holds a block of this many positions and
        # a step runs one forward over it (``_block_round``).
        self.block_len = knobs["block_len"]
        # A lane holds recurrent state beside its K/V rows (the
        # spec's block says so). A decode step then advances only
        # the lanes ``cache.live`` names; the engine owns that mask and
        # uploads it when the decoding set changes (as it does a paged
        # table), never on a steady step.
        self.recurrent = knobs["recurrent"]
        self._live_lanes: tuple = ()
        # The engine drives ONE device; it has no mesh (ROADMAP C9).
        # Weights and every piece of engine state are COMMITTED to it
        # up front: a restored checkpoint's arrays are committed, jit
        # keys its cache on that, and a fresh uncommitted cache made
        # warmup's first program compile a second time on the first
        # request (the static-shape pin, broken only under restored
        # weights).
        self._device = jax.local_devices()[0]
        self.draft_spec = draft_spec
        self.draft_params = self._put(draft_params)
        ctx_len = knobs["ctx_len"]
        tokens_per_decode = knobs["tokens_per_decode"]
        chunk = knobs["chunk"]
        min_bucket = knobs["min_bucket"]
        self.spec = spec
        self.params = self._put(params)
        # Model lifecycle (serve/lifecycle.py): the serving version
        # label (None keeps every surface byte-identical to the
        # pre-lifecycle engine), hot-swap counters, and the admission
        # pause that drains lanes to the swap barrier WITHOUT dropping
        # queued or newly-submitted work.
        self.model_version = model_version
        self.reloads_total = 0
        self.rollbacks_total = 0
        self._admission_paused = False
        self.num_slots = slots
        self.prefill_len = prefill_len
        self.prefill_chunk = chunk
        self.min_bucket = min_bucket
        self._ctx_len = ctx_len
        self._tokens_per_decode = tokens_per_decode
        self.step_token_budget = knobs["step_token_budget"]
        if admit_every < 0:
            raise ValueError(
                f"admit_every must be >= 0, got {admit_every}"
            )
        self.admit_every = int(admit_every)
        self._last_admit_step: Optional[int] = None
        self.clock = clock
        self.metrics = metrics or MetricsWriter(None)
        # Span tracing (ddp_tpu.obs): the step, its retire and admit
        # parts, each chunk and decode DISPATCH and the blocking token
        # fetch land in the tracer's always-on ring (and, while a
        # profiler session is open, in its trace). Host spans record
        # host time and never sync the device: device time is read
        # from the profiler's trace by program name. The process-
        # global tracer unless the caller hands one in, so a reader
        # can reach the spans after the engine is gone.
        self.tracer = tracer if tracer is not None else get_tracer()
        # Runtime sanitizer (--sanitize, runtime/sanitize.py): the
        # transfer guard arms around the steady-state DECODE dispatch
        # in step(), proving it does zero implicit host transfer. The
        # engine's two deliberate transfers — the chunk-argument
        # upload and the one-step-behind [S] int32 fetch — execute
        # OUTSIDE the guarded region (no allow() windows here, unlike
        # the trainer). Disabled = a free nullcontext.
        from ddp_tpu.runtime.sanitize import Sanitizer

        self._sanitizer = Sanitizer(sanitize)
        self._started_at = clock()
        self._productive_s = 0.0
        # Per-request 64-bit trace-id space: deterministic when the
        # caller seeds it (tests), collision-free across replicas when
        # left to entropy (scripts/serve.py default) — merged fleet
        # traces must keep requests from different engines apart.
        import os as _os

        if trace_seed is None:
            trace_seed = int.from_bytes(_os.urandom(8), "little")
        self.scheduler = Scheduler(
            max_queue=max_queue,
            prefill_len=prefill_len,
            total_len=ctx_len,
            vocab_size=spec.vocab_size,
            chunk=chunk,
            min_bucket=min_bucket,
            token_budget=self.step_token_budget,
            trace_seed=trace_seed,
            clock=clock,
        )
        # Request-level distributed tracing (obs/reqtrace.py): OFF by
        # default and pinned free when off — every recording site
        # below guards on one `is not None` check, so a disabled
        # engine allocates no per-request trace state at all. Enabled,
        # events are stamped only at points the engine already touches
        # the host (the PR-3 transfer invariant holds under
        # --sanitize, re-pinned by tests/test_reqtrace.py).
        from ddp_tpu.obs.reqtrace import RequestTracer

        self._reqtrace = (
            RequestTracer(keep=reqtrace_keep) if reqtrace else None
        )
        # Fleet trace adoption accounting (ISSUE 19): valid inbound
        # contexts adopted vs malformed ones orphaned (counted, never
        # fatal); router-staged hop seconds parked per rid until the
        # completion's serve_request record picks them up.
        self.trace_propagated = 0
        self.trace_orphaned = 0
        self._request_hops: dict[int, dict] = {}
        # SLO engine (obs/slo.py): observes every retired request;
        # breach transitions land in the metrics stream AND the flight
        # recorder (the PR-4 post-mortem ring) before any caller hook.
        from ddp_tpu.obs.recorder import FlightRecorder, build_info

        self._recorder = recorder if recorder is not None else (
            FlightRecorder(None)
        )
        self._slo = slo
        self._user_breach_cb = None
        if slo is not None:
            prev = slo.on_breach
            # Re-attachment (a fresh engine over the same SLOEngine,
            # e.g. a restart loop): adopt the ORIGINAL caller hook,
            # not the dead engine's interceptor — chaining through it
            # would duplicate breach records into a retired engine's
            # metrics/flight streams.
            if (
                getattr(prev, "__func__", None)
                is ServeEngine._on_slo_breach
            ):
                prev = prev.__self__._user_breach_cb
            self._user_breach_cb = prev
            slo.on_breach = self._on_slo_breach
        self._build_info = build_info()
        # {min_bucket · 2^i} ∪ {chunk}: the whole compiled-width set.
        self.buckets = self.scheduler.bucket_list()
        t_lanes = time.perf_counter()
        self._slots = [_Slot(index=i) for i in range(slots)]
        cache_dtype = jnp.int8 if kv_dtype == "int8" else jnp.float32
        lane_dtype = getattr(block_module(spec), "lane_dtype", None)
        if lane_dtype:
            cache_dtype = lane_dtype(params)
        if self.paged:
            self._cache = self._put(init_paged_slot_cache(
                spec, slots,
                num_pages=self.kv_pages, page_size=self.page_size,
                dtype=cache_dtype,
            ))
            self._prefix = PrefixCache(self.kv_pages, self.page_size)
            # Host mirror of the device page table: mutated at
            # bind/retire, uploaded (one [S, lane_pages] int32 array)
            # before the next dispatch — the steady-state decode loop
            # still transfers nothing but the [S] token vector.
            self._table_np = np.zeros(
                (slots, self._lane_pages), np.int32
            )
            self._table_dirty = False
            # Admission stalls where the FIFO head's page demand
            # outran the pool (requeued, retried next step).
            self.page_starved_binds = 0
        else:
            self._cache = self._put(init_slot_cache(
                spec, slots, dtype=cache_dtype,
            ))
        # Device-resident token vector: output of the last decode (or
        # chunk splice), input to the next — the decode loop never
        # routes tokens through the host. NOT donated anywhere: the
        # host still owes an async read of the previous step's values.
        self._toks = self._put(jnp.zeros((slots,), jnp.int32))
        # Per-slot sampling state, ALSO device-resident: the chunk
        # program installs a request's (seed, temperature, top_p) at
        # its lane and the decode program advances the fold_in step
        # counters — the steady-state loop uploads NOTHING per step.
        # Seeds are int32 to hit exactly generate()'s
        # jax.random.key(seed) path.
        self._seeds = self._put(jnp.zeros((slots,), jnp.int32))
        self._sample_steps = self._put(jnp.zeros((slots,), jnp.int32))
        self._temps = self._put(jnp.zeros((slots,), jnp.float32))
        self._top_ps = self._put(jnp.ones((slots,), jnp.float32))
        self.tracer.phase_complete(
            "startup.lane_cache", t_lanes, time.perf_counter() - t_lanes,
            parent=t_init,
        )
        # Device values dispatched but not yet read back:
        # ("first", scalar, slot) | ("decode", [S] array, lanes).
        self._pending: list[tuple[str, Any, Any]] = []
        # name -> (program, its arguments' shapes) of what warmup() built
        self._warmed: dict[str, tuple] = {}
        self._completed: dict[int, Completion] = {}
        self._steps = 0
        self.ttft = StatSummary()
        self.decode_rate = StatSummary()
        self.step_latency = StatSummary()
        # User-facing latency SLIs, always on (two float appends per
        # request): queue wait (submit → lane bind) and TPOT (decode
        # seconds per output token) — what the SLO engine evaluates
        # and the fleet aggregator merges.
        self.queue_wait = StatSummary()
        self.tpot = StatSummary()
        # Monotone token counter (the aggregator's tokens/s source —
        # per-request rate summaries are not additive across a fleet).
        self.tokens_emitted_total = 0
        # What the banded lane read is for, per plain decode step: the
        # cache rows the decoding lanes attend (each lane's pos + 1)
        # against the rows the lanes hold (slots x total_len). Their
        # quotient is the share of lane bytes a step has to fetch —
        # host arithmetic on positions the engine tracks anyway.
        self.kv_rows_attended_total = 0
        self.kv_rows_lane_total = 0
        # Monotone count of requests accepted into the queue: what a
        # load that hands requests over in order waits on.
        self.accepted_total = 0
        # What the frontend adds to a request's latency outside the
        # engine's own clock (serve/server.py feeds both under its
        # lock): the wait for that lock before ``submit``, and finish
        # to the answer being picked up. ``ttft_s`` and ``queue_s``
        # start only once the lock is won.
        self.lock_wait = StatSummary()
        self.pickup = StatSummary()
        # Recent retirement clock times (bounded): the queue-drain-rate
        # window behind ``queue_drain_eta_s`` — what a backpressure
        # 429's Retry-After is derived from, so a rejected client (or
        # the fleet router) backs off for as long as the queue will
        # actually take to drain instead of hammering.
        self._retire_times: deque = deque(maxlen=32)
        # Monotone aggregate counters (the /metricsz exposition needs
        # totals, not just the JSONL event stream): admission rejects
        # by reason, finished requests by status.
        self.reject_counts: dict[str, int] = {}
        self.status_counts: dict[str, int] = {}
        # The engine's entire compiled surface: ONE decode program
        # (sampling fused) plus per bucket width one FIRST-chunk
        # program (self-contained causal attention — short prompts pay
        # bucket-sized compute, the monolithic-prefill cost) and one
        # CONTINUATION program (banded attention against the full
        # lane) — slot index / start / length / final / sampling
        # config are all traced, so no request mix can grow the set
        # past 2·len(buckets) + 1 after warmup(). Fresh lambdas (not
        # bare function objects): jit tracing caches are shared per
        # function object, and the static-shape pin must be
        # per-engine. Each gets a name of its own, which is how the
        # profiler's trace tells the programs apart
        # (``jit_serve_decode``, ``jit_serve_prefill_first``, ...).
        # The one-token programs are the spec's block's: a block with
        # a module of its own brings its chunk and decode functions
        # under the GPT-2 path's signatures, so everything below, and
        # the step loop, holds three jitted callables and asks no more.
        module = block_module(spec)
        # K/V of more than one kind in a lane: what a decode step
        # reads of each is the module's to count.
        self._attended_rows = getattr(module, "attended_rows", None)
        # Keys selected before they are attended: what the indexer
        # scored and attention read is the module's to count. What a
        # decode step selected in ONE lane (``cache.sel``) is copied
        # out by a small program, for a request that asked and at its
        # last step only.
        self._dsa_rows = getattr(module, "dsa_rows", None)
        if self._dsa_rows:
            self._lane_selection = jax.jit(_named_fn(
                "serve_lane_selection",
                lambda sel, i: lax.dynamic_index_in_dim(
                    sel, i, axis=1, keepdims=False),
            ))
        if module:
            _prefill_chunk = module.prefill_chunk
            _decode_sample = module.slot_decode_sample_step
        else:
            _prefill_chunk, _decode_sample = _gpt2_chunk, _gpt2_decode

        _named = _named_fn

        def _chunk_fn(name, lane_attend, chunk_spec):
            return jax.jit(
                _named(name, lambda p, c, t, se, sp, tm, tp, s, ch, st,
                       ln, fi, sd, rtm, rtp: _prefill_chunk(
                    chunk_spec, p, c, t, se, sp, tm, tp, s, ch, st, ln,
                    fi, sd, rtm, rtp, lane_attend=lane_attend,
                )),
                donate_argnums=(1,),
            )

        # Compiled-program introspection (obs/xprof.py): when a live
        # Xprof is passed, the engine's whole program set dispatches
        # through its compile ledger — warmup() then enumerates every
        # program WITH its compile time, XLA FLOPs, and memory
        # breakdown, and /metricsz gains compile + HBM gauges. The
        # wrapper preserves _cache_size(), so the static-shape pins
        # (compile_counts frozen after warmup) hold either way. None =
        # uninstrumented, byte-identical to the pre-xprof engine.
        from ddp_tpu.obs.xprof import DeviceMemorySampler, Xprof

        self._xprof = xprof if xprof is not None else Xprof(enabled=False)
        self._hbm = DeviceMemorySampler(enabled=self._xprof.enabled)
        self._chunk_first = self._xprof.instrument(
            _chunk_fn("serve_prefill_first", False, spec),
            "serve.prefill_first"
        )
        self._chunk_cont = self._xprof.instrument(
            _chunk_fn("serve_prefill_chunk", True, spec),
            "serve.prefill_chunk"
        )
        impl = self.decode_attn
        self._decode = self._xprof.instrument(
            jax.jit(
                _named("serve_decode", lambda p, c, t, sd, st, tm, tp:
                       _decode_sample(
                    spec, p, c, t, sd, st, tm, tp, attn_impl=impl
                )),
                donate_argnums=(1,),
            ),
            # The label names the program actually built: recompile
            # culprits and /metricsz compile gauges distinguish the
            # kernel path from the jnp path.
            "serve.flash_decode" if impl == "flash" else "serve.decode",
        )
        if impl == "flash":
            # The kernel snaps block_k to a tile-aligned divisor of the
            # lane length that fits VMEM (ops/decode.decode_block) — a
            # host-side decision XLA introspection can't see. Ledger
            # it so the reader sees the EFFECTIVE block, not the
            # requested default.
            self._xprof.annotate(
                "serve.flash_decode",
                block_k_requested=DEFAULT_BLOCK_K,
                block_k=knobs["decode_block_k"],
            )
        if self.spec_tokens:
            dspec = draft_spec
            # Draft-side machinery: its OWN cache (the draft tracks
            # the same token history at its own width) plus dummy
            # per-slot sampling state for the chunk signature — the
            # draft always proposes greedily, so none of it is read.
            self._draft_cache = self._put(init_slot_cache(dspec, slots))
            self._d_toks = self._put(jnp.zeros((slots,), jnp.int32))
            self._d_seeds = self._put(jnp.zeros((slots,), jnp.int32))
            self._d_steps = self._put(jnp.zeros((slots,), jnp.int32))
            self._d_temps = self._put(jnp.zeros((slots,), jnp.float32))
            self._d_top_ps = self._put(jnp.ones((slots,), jnp.float32))
            # Device-resident sync flags: the first draft step of a
            # round adopts the TARGET cache's per-lane positions (the
            # draft advanced spec_tokens last round, the target only
            # as far as acceptance went). Prebuilt so the sanitized
            # hot loop uploads nothing.
            self._sync_pos = self._put(jnp.asarray(True))
            self._keep_pos = self._put(jnp.asarray(False))

            def _draft_propose(p, c, t, pos, sync):
                c = c._replace(pos=jnp.where(sync, pos, c.pos))
                logits, c = _decode_step(
                    dspec, p, c, t, attn_impl=impl
                )
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), c

            self._draft_chunk_first = self._xprof.instrument(
                _chunk_fn("serve_draft_prefill_first", False, dspec),
                "serve.draft_prefill_first"
            )
            self._draft_chunk_cont = self._xprof.instrument(
                _chunk_fn("serve_draft_prefill_chunk", True, dspec),
                "serve.draft_prefill_chunk"
            )
            self._draft_decode = self._xprof.instrument(
                jax.jit(
                    _named("serve_draft_decode", _draft_propose),
                    donate_argnums=(1,),
                ),
                "serve.draft_decode",
            )
            self._verify = self._xprof.instrument(
                jax.jit(
                    _named("serve_spec_verify", lambda p, c, t, dr, sd,
                           st, tm, tp: _verify_step(
                        spec, p, c, t, dr, sd, st, tm, tp
                    )),
                    donate_argnums=(1,),
                ),
                "serve.spec_verify",
            )
        if self.block_len:
            # A lane "in a block": the block's tokens and mask (and the
            # sampling state) live on the device with the lane
            # (sdar.BlockLanes, donated through both programs like the
            # cache). The same two chunk programs per bucket, under the
            # block-causal mask and owing no token, and ONE block-step
            # program in the decode program's place: forward, choice,
            # unmasking and, for lanes whose block is clean, the
            # commit, for all lanes in one call.
            self._lanes = self._put(_sdar.init_block_lanes(spec, slots))

            def _block_chunk_fn(name, lane_attend):
                return jax.jit(
                    _named(name, lambda p, c, ln, *a: _sdar.prefill_chunk(
                        spec, p, c, ln, *a, lane_attend=lane_attend,
                    )),
                    donate_argnums=(1, 2),
                )

            self._chunk_first = self._xprof.instrument(
                _block_chunk_fn("serve_prefill_first", False),
                "serve.prefill_first",
            )
            self._chunk_cont = self._xprof.instrument(
                _block_chunk_fn("serve_prefill_chunk", True),
                "serve.prefill_chunk",
            )
            self._decode = self._xprof.instrument(
                jax.jit(
                    _named("serve_block_step", lambda p, c, ln:
                           _sdar.block_step(spec, p, c, ln, attn_impl=impl)),
                    donate_argnums=(1, 2),
                ),
                "serve.block_step",
            )
        # Block-diffusion tallies, from what the device reports one
        # step behind: lane-forwards of generating lanes, blocks and
        # tokens COMMITTED (never forward positions, never more than a
        # request asked for); and the expert layer's routing counts
        # (rows routed, the fullest expert's rows summed over layers
        # and steps beside the last step's fullest, experts hit).
        self.block_forwards_total = 0
        self.blocks_committed_total = 0
        self.tokens_committed_total = 0
        self.positions_unmasked_total = 0
        self.moe_tokens_routed_total = 0
        self.moe_expert_load_max_sum = 0
        self.moe_expert_load_max = 0
        self.moe_experts_hit_total = 0
        self.moe_layer_calls_total = 0
        self._last_round = (0, 0)  # (unmasked, committed) last fetched
        # Recurrent-lane tallies, host arithmetic like the rows above:
        # live lanes summed over decode steps (each is every recurrent
        # layer's state read and written once), real prompt positions
        # through the chunked scan, and lanes reset at admission.
        self.ssm_lane_updates_total = 0
        self.ssm_prefill_tokens_total = 0
        self.ssm_state_resets_total = 0
        # A lane with a ring and shared rows (``attended_rows``): rows
        # a decode step read of each kind, summed over live lanes,
        # reading layers and steps; and a prompt's real positions
        # through the layers that write a lane and through those that
        # only sample (whichever program ran them).
        self.kv_ring_rows_attended_total = 0
        self.kv_shared_rows_attended_total = 0
        self.prefill_self_positions_total = 0
        self.prefill_cross_positions_total = 0
        # A lane whose keys are selected (``dsa_rows``): rows the
        # indexer scored and rows attention read, over real positions,
        # live lanes and layers (host arithmetic); and the expert
        # layers' (expert, token) pairs routed and those whose expert
        # is held here, one more output of each program, fetched a
        # step behind (``pairs``).
        self.dsa_rows_scored_total = 0
        self.dsa_rows_selected_total = 0
        self.moe_pairs_routed_total = 0
        self.moe_pairs_held_total = 0
        # Engine-lifetime speculative tallies (the /stats + bench
        # acceptance-rate source); zero-cost when speculation is off.
        self.spec_drafted_total = 0
        self.spec_accepted_total = 0
        self.accept_rate = StatSummary()
        self.tracer.phase_complete(
            "startup.state", t_init, time.perf_counter() - t_init,
            nums=("engine",),
        )

    def _put(self, tree):
        """Commit a pytree of arrays to the engine's device (a no-op
        for leaves already there)."""
        return jax.device_put(tree, self._device)

    # ---- frontend surface ------------------------------------------

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        *,
        temperature: float = 0.0,
        top_p: float = 1.0,
        seed: int = 0,
        timeout: Optional[float] = None,
        trace: Optional[str] = None,
        hops: Optional[dict] = None,
        model: Optional[str] = None,
        record_blocks: bool = False,
        record_selection: bool = False,
    ) -> Admission:
        """Admission-checked enqueue; rejections carry a reason.

        ``record_blocks`` (a model that generates by blocks only):
        keep every forward's block inputs for the completion.
        ``record_selection`` (a model that selects its keys only): keep
        what the last token's step selected.

        ``trace`` is an inbound fleet trace-context line (the router's
        ``00-<trace>-<span>-<parent>``): a VALID one is adopted — the
        request's trace id becomes the router's, its parent span the
        router attempt's — and counted ``trace_propagated``; a
        present-but-malformed one is counted ``trace_orphaned`` and
        the engine mints locally, exactly as if nothing arrived (a
        peer's garbage must never reject a request). ``hops`` is the
        router's staging hop seconds (queue/handoff/migrate), stamped
        onto this request's ``serve_request`` record so one record
        answers "which hop paid".
        """
        from ddp_tpu.obs.reqtrace import parse_trace_context

        adopted = None
        if trace is not None:
            adopted = parse_trace_context(trace)
            if adopted is None:
                self.trace_orphaned += 1
            else:
                self.trace_propagated += 1
        adm = self.scheduler.submit(
            prompt,
            max_new_tokens,
            temperature=temperature,
            top_p=top_p,
            seed=seed,
            timeout=timeout,
            trace_id=adopted[0] if adopted else None,
            model=model,
            record_blocks=bool(record_blocks) and bool(self.block_len),
            record_selection=bool(record_selection) and bool(self._dsa_rows),
        )
        if not adm.accepted:
            self.reject_counts[adm.reason] = (
                self.reject_counts.get(adm.reason, 0) + 1
            )
            self.metrics.write(
                "serve_reject",
                reason=adm.reason,
                queue_depth=self.scheduler.depth,
            )
            return adm
        self.accepted_total += 1
        if hops:
            self._request_hops[adm.request.rid] = dict(hops)
        if self._reqtrace is not None:
            # The admit event: the request's 64-bit trace id exists
            # from this point on (assigned by the scheduler — or
            # adopted from the router), and the submit call is already
            # a host-side touch point.
            self._reqtrace.admit(
                adm.request.rid,
                adm.request.trace_id,
                parent=f"{adopted[1]:016x}" if adopted else None,
            )
        return adm

    def result(self, rid: int) -> Optional[Completion]:
        """The finished record for ``rid``, None while pending."""
        return self._completed.get(rid)

    def pop_result(self, rid: int) -> Optional[Completion]:
        return self._completed.pop(rid, None)

    @property
    def active(self) -> int:
        return sum(1 for s in self._slots if not s.free)

    @property
    def pending(self) -> bool:
        # Admission-paused (hot-swap barrier): queued work is not
        # steppable — only running lanes keep the loop hot, so a
        # paused engine with an empty batch idles instead of spinning
        # empty steps (and their serve_step records) while the swap's
        # host-side load runs.
        if self._admission_paused:
            return self.active > 0
        return self.active > 0 or self.scheduler.depth > 0

    def compile_counts(self) -> dict[str, int]:
        """Compiled-program count per engine function (the static-
        shape pin: after ``warmup()`` these must never grow;
        prefill_first and prefill_chunk are each bounded by
        ``len(self.buckets)``). Speculative engines add the draft
        chunk programs (same bucket bound), one draft-decode and one
        verify program."""
        counts = {
            "prefill_first": self._chunk_first._cache_size(),
            "prefill_chunk": self._chunk_cont._cache_size(),
            # the block step stands in the decode program's place
            "block_step" if self.block_len else "decode":
                self._decode._cache_size(),
        }
        if self._dsa_rows:
            counts["lane_selection"] = self._lane_selection._cache_size()
        if self.spec_tokens:
            counts.update(
                draft_prefill_first=self._draft_chunk_first._cache_size(),
                draft_prefill_chunk=self._draft_chunk_cont._cache_size(),
                draft_decode=self._draft_decode._cache_size(),
                spec_verify=self._verify._cache_size(),
            )
        return counts

    def compile_budget(self) -> int:
        """The engine's whole-program-set ceiling: 2 chunk programs
        per bucket + 1 decode, doubled-chunks + draft-decode + verify
        when speculating — asserted by the static-shape tests
        (tests/test_serve.py, tests/test_flash_decode.py)."""
        base = 2 * len(self.buckets) + 1 + bool(self._dsa_rows)
        if self.spec_tokens:
            base += 2 * len(self.buckets) + 2
        return base

    def warmup(self) -> dict[str, int]:
        """Eagerly compile the engine's whole program set → counts.

        Per bucket width one first-chunk and one continuation-chunk
        program, plus the decode program — after this, steady state
        compiles NOTHING (the zero-recompilation pin's baseline, and
        a serving process's first-request latency is a decode step,
        not an XLA compile). Must run on an idle engine: warmup
        chunks write garbage K/V into lane 0, which the refill
        invariant (every line is overwritten before it becomes
        attendable) makes harmless only while no request owns a lane.
        """
        if self.active:
            raise RuntimeError("warmup() requires an idle engine")
        # Kept records of the process's start (obs/tracer.py): the
        # warm-up, under it each call that builds one program (as many
        # as ``compile_counts()`` sums to), then the wait for the
        # device. What each call traced, lowered and loaded is in the
        # ``compile.*`` records its interval contains.
        tracer = self.tracer
        with tracer.phase("startup.warmup") as warm:

            def program(name, width=0):
                return tracer.phase(
                    "startup.warmup_program", parent=warm.t0,
                    nums=(name, width),
                )

            last = (self._warm_blocks if self.block_len
                    else self._warm_tokens)(program)
            with tracer.phase("startup.warmup_wait", parent=warm.t0):
                jax.block_until_ready(last)
            counts = self.compile_counts()
            warm.nums = (sum(counts.values()),)
        return counts

    def program_hlo(self) -> dict[str, str]:
        """The compiled text of each one-token program ``warmup()``
        built, by ``serve_<program>[:<width>]``: what maps a device
        operation's instruction name to the JAX name stack (and with it
        the ``jax.named_scope``) it came from; the profiler's trace
        keeps the former alone. Lowers and compiles anew (a persistent
        compile cache answers): not for a measured window."""
        out = {}
        for name, (fn, args) in self._warmed.items():
            lower = getattr(fn, "lower", None)
            if lower is not None:
                out[name] = lower(*args).compile().as_text()
        return out

    def _chunk_programs(self):
        return (("prefill_first", self._chunk_first),
                ("prefill_chunk", self._chunk_cont))

    def _warm_blocks(self, program):
        """Block diffusion's program set, each call under
        ``program(name, width)`` → what the last call returned."""
        # The chunk programs are warmed with what a step hands them
        # (numpy scalars, uploaded with the call): an argument of
        # another kind is another entry in the jit cache.
        czero, off, cold, whole = (
            np.int32(0), np.bool_(False), np.float32(0.0), np.float32(1.0))
        tail = np.zeros((self.block_len,), np.int32)
        for name, fn in self._chunk_programs():
            for w in self.buckets:
                with program(name, w):
                    self._cache, self._lanes, _ = fn(
                        self.params, self._cache, self._lanes, czero,
                        np.zeros((w,), np.int32), czero, np.int32(w),
                        off, tail, czero, czero, czero, cold, whole,
                    )
        with program("block_step"):
            self._cache, self._lanes, report, _ = self._decode(
                self.params, self._cache, self._lanes
            )
        return report

    def _warm_tokens(self, program):
        """The one-token program set (and the speculative one), as
        ``_warm_blocks``."""
        czero, off, cold, whole = (
            np.int32(0), np.bool_(False), np.float32(0.0), np.float32(1.0))
        zero = jnp.int32(0)
        for name, fn in self._chunk_programs():
            for w in self.buckets:
                args = (
                    self.params, self._cache, self._toks, self._seeds,
                    self._sample_steps, self._temps, self._top_ps,
                    czero, np.zeros((w,), np.int32), czero,
                    np.int32(w), off, czero, cold, whole,
                )
                self._warmed[f"serve_{name}:{w}"] = (fn, _abstract(args))
                with program(name, w):
                    (self._cache, self._toks, self._seeds,
                     self._sample_steps, self._temps, self._top_ps,
                     *_) = fn(*args)
        args = (self.params, self._cache, self._toks, self._seeds,
                self._sample_steps, self._temps, self._top_ps)
        self._warmed["serve_decode"] = (self._decode, _abstract(args))
        with program("decode"):
            (self._toks, self._cache, self._sample_steps,
             *_) = self._decode(*args)
        if self._dsa_rows:
            with program("lane_selection"):
                self._lane_selection(self._cache.sel, np.int32(0))
        if self.spec_tokens:
            for name, fn in (
                ("draft_prefill_first", self._draft_chunk_first),
                ("draft_prefill_chunk", self._draft_chunk_cont),
            ):
                for w in self.buckets:
                    with program(name, w):
                        (self._draft_cache, self._d_toks, self._d_seeds,
                         self._d_steps, self._d_temps, self._d_top_ps,
                         _) = fn(
                            self.draft_params, self._draft_cache,
                            self._d_toks, self._d_seeds, self._d_steps,
                            self._d_temps, self._d_top_ps,
                            zero, jnp.zeros((w,), jnp.int32), zero,
                            jnp.int32(w), jnp.asarray(False), zero,
                            jnp.float32(0.0), jnp.float32(1.0),
                        )
            with program("draft_decode"):
                _, self._draft_cache = self._draft_decode(
                    self.draft_params, self._draft_cache, self._toks,
                    self._cache.pos, self._sync_pos,
                )
            with program("spec_verify"):
                (self._toks, self._cache, self._sample_steps, _t, _m
                 ) = self._verify(
                    self.params, self._cache, self._toks,
                    jnp.zeros((self.num_slots, self.spec_tokens),
                              jnp.int32),
                    self._seeds, self._sample_steps, self._temps,
                    self._top_ps,
                )
        return self._toks

    # ---- model lifecycle (serve/lifecycle.py) -----------------------

    def pause_admission(self) -> None:
        """Stop BINDING queued requests to lanes (step() skips the
        admit phase) while running lanes decode to completion — the
        drain-to-a-barrier half of a hot-swap. Unlike the server's
        drain (503s new work to a replacement process), paused
        admission keeps accepting submissions into the queue: nothing
        is dropped across a swap, requests just wait it out."""
        self._admission_paused = True

    def resume_admission(self) -> None:
        self._admission_paused = False

    def install_params(
        self,
        params: Any,
        *,
        model_version: Optional[str] = None,
        invalidate_prefix: bool = False,
    ) -> None:
        """Atomically swap the serving weights in place.

        Requires a drained engine (``active == 0`` — pause admission
        and let the lanes retire first). The compiled program set is
        untouched: params are a per-dispatch argument (only the cache
        is donated), so a same-shaped tree swaps with ZERO
        recompilation — which is also why the tree must match the
        serving one exactly (structure, shapes, dtypes); a skew here
        raises before any state changes and the caller rolls back.
        The old leaves are released by reference drop, never
        ``.delete()``d — callers legitimately install the same tree
        they are serving (the token-identity drill) or hold the old
        tree for rollback.

        ``invalidate_prefix`` flushes the radix prefix index and page
        table (paged engines): cached K/V was computed under the OLD
        weights, so any version change must drop it; a same-version
        reinstall keeps the cache (and token identity) intact.
        """
        if self.active:
            raise RuntimeError(
                "install_params() requires a drained engine "
                f"({self.active} lanes still bound — pause admission "
                "and wait for retirement)"
            )
        old_leaves, old_def = jax.tree.flatten(self.params)
        new_leaves, new_def = jax.tree.flatten(params)
        if old_def != new_def:
            raise ValueError(
                "spec_skew: incoming parameter tree structure differs "
                "from the serving tree"
            )
        for old, new in zip(old_leaves, new_leaves):
            if (
                tuple(old.shape) != tuple(new.shape)
                or old.dtype != new.dtype
            ):
                raise ValueError(
                    f"spec_skew: leaf {tuple(new.shape)}/{new.dtype} "
                    f"!= serving {tuple(old.shape)}/{old.dtype}"
                )
        self.params = self._put(params)
        if model_version is not None:
            self.model_version = model_version
        self.reloads_total += 1
        if invalidate_prefix and self.paged:
            # No lane owns pages at the barrier, so every mapped page
            # belongs to the (now stale) prefix index: rebuild it and
            # zero the host table — exactly the startup state, with
            # the pool's garbage bytes unreferenced until re-written.
            self._prefix = PrefixCache(self.kv_pages, self.page_size)
            self._table_np[:] = 0
            self._table_dirty = True

    def kv_bytes_per_slot(self) -> int:
        """KV-cache HBM per decode lane, scales included — the number
        int8 quantization halves (better: int8 rows + one fp32 scale
        per head per position vs fp32 rows)."""
        leaves = [self._cache.k, self._cache.v]
        if self._cache.quantized():
            leaves += [self._cache.k_scale, self._cache.v_scale]
        return sum(int(x.nbytes) for x in leaves) // self.num_slots

    def ring_bytes_per_slot(self) -> int:
        """K/V rows one lane holds in rings (windowed layers); 0 for a
        model without them."""
        leaves = (getattr(self._cache, "ring_k", ()),
                  getattr(self._cache, "ring_v", ()))
        return sum(int(x.nbytes) for x in leaves
                   if hasattr(x, "nbytes")) // self.num_slots

    def state_bytes_per_slot(self) -> int:
        """Recurrent state per lane (every Mamba layer's state and
        convolution tail); 0 for a model without such layers."""
        if not self.recurrent:
            return 0
        leaves = (self._cache.ssm, self._cache.conv)
        return sum(int(x.nbytes) for x in leaves) // self.num_slots

    def cache_bytes_per_slot(self) -> int:
        """HBM one decode lane holds, K/V and recurrent state both —
        and with it how many ``slots`` a chip holds."""
        return (self.kv_bytes_per_slot() + self.ring_bytes_per_slot()
                + self.state_bytes_per_slot() + self.latent_bytes_per_slot())

    def recurrent_stats(self) -> dict:
        """The recurrent lanes' counters and gauges (``/stats``'s
        ``recurrent_state``, ``/metricsz``'s ``ddp_tpu_serve_ssm_*``);
        plain host ints, readable without the server's lock."""
        return {
            "ssm_lane_updates_total": self.ssm_lane_updates_total,
            "ssm_prefill_tokens_total": self.ssm_prefill_tokens_total,
            "ssm_state_resets_total": self.ssm_state_resets_total,
            "ssm_state_bytes_per_slot": self.state_bytes_per_slot(),
            "kv_bytes_per_slot": self.kv_bytes_per_slot(),
            **({
                "kv_ring_rows_attended_total":
                    self.kv_ring_rows_attended_total,
                "kv_shared_rows_attended_total":
                    self.kv_shared_rows_attended_total,
                "prefill_self_positions_total":
                    self.prefill_self_positions_total,
                "prefill_cross_positions_total":
                    self.prefill_cross_positions_total,
                "kv_ring_bytes_per_slot": self.ring_bytes_per_slot(),
                "kv_shared_bytes_per_slot": self.kv_bytes_per_slot(),
            } if self._attended_rows else {}),
        }

    def latent_bytes_per_slot(self) -> int:
        """Latent and indexer rows one lane holds over all layers; 0
        for a model without them."""
        leaves = (jax.tree.leaves(getattr(self._cache, "latent", ()))
                  + jax.tree.leaves(getattr(self._cache, "index_k", ())))
        return sum(int(x.nbytes) for x in leaves) // self.num_slots

    def latent_stats(self) -> dict:
        """The selected-key lanes' counters and gauge (``/stats``'s
        ``latent_attention``, ``/metricsz``'s ``ddp_tpu_serve_dsa_*``
        and ``ddp_tpu_serve_moe_pairs_*``); plain host ints."""
        return {
            "dsa_rows_scored_total": self.dsa_rows_scored_total,
            "dsa_rows_selected_total": self.dsa_rows_selected_total,
            "moe_pairs_routed_total": self.moe_pairs_routed_total,
            "moe_pairs_held_total": self.moe_pairs_held_total,
            "latent_bytes_per_slot": self.latent_bytes_per_slot(),
        }

    def page_stats(self) -> Optional[dict]:
        """Paged-mode pool/index snapshot (None on fixed-lane engines
        — the /metricsz absent-key gate). ``effective_slots_multiplier``
        is the reuse win: pages the lane-copies baseline would keep
        resident (Σ per-lane mappings) over the UNIQUE mapped pages —
        1.0 with no sharing, > 1 when prefix pages are forked."""
        if not self.paged:
            return None
        refs = self._prefix.mapped_page_refs
        uniq = self._prefix.mapped_pages
        return {
            **self._prefix.stats(),
            "lane_pages": self._lane_pages,
            "pages_mapped": uniq,
            "page_starved_binds": self.page_starved_binds,
            "effective_slots_multiplier": (
                round(refs / uniq, 3) if uniq else None
            ),
        }

    # ---- disaggregated serving: page export/install (PR 16) ---------

    def export_prefix(self, tokens, trace=None) -> Optional[bytes]:
        """Ship a cached prefix's raw pages (serve/disagg.py wire
        format): the longest indexed prefix of ``tokens``, at page
        granularity, K/V bytes (plus per-page scales on int8 pools)
        gathered from the pool → one self-validating binary payload.
        None on fixed-lane engines or when no full page of the prompt
        is cached (the /pages/export 404).

        The gather is a host read OUTSIDE the decode loop (migration
        is a control-plane event, like the bind-time table upload) —
        the steady-state transfer invariant is untouched.
        """
        if self.recurrent or self._dsa_rows:
            why = RECURRENT_REFUSALS if self.recurrent else LATENT_REFUSALS
            raise ValueError(
                f"export_prefix does not apply to the {self.spec.block} block: "
                f"{why['export_prefix']}"
            )
        if not self.paged:
            return None
        from ddp_tpu.serve.disagg import encode_pages

        pids = self._prefix.match(
            list(tokens), len(tokens) // self.page_size
        )
        if not pids:
            return None
        idx = jnp.asarray(pids, jnp.int32)
        covered = list(tokens)[: len(pids) * self.page_size]
        quant = self._cache.quantized()
        return encode_pages(
            covered,
            np.asarray(self._cache.k[:, idx]),
            np.asarray(self._cache.v[:, idx]),
            page_size=self.page_size,
            k_scale=(
                np.asarray(self._cache.k_scale[:, idx]) if quant
                else None
            ),
            v_scale=(
                np.asarray(self._cache.v_scale[:, idx]) if quant
                else None
            ),
            table_row=pids,
            positions=len(covered),
            # Fleet trace context threaded by the router: rides the
            # DPKV header so the install side of the migration sees
            # the same trace id (absent-key byte-identical when off).
            trace=trace,
            # Serving identity: lets the install side refuse pages
            # computed under a different model mid-/reloadz (absent on
            # version-less engines — pre-lifecycle wire bytes).
            model_version=self.model_version,
        )

    def install_prefix(self, frame) -> Optional[dict]:
        """Host another replica's prefilled pages (the POST /pages
        implementation): validate the frame against THIS pool's
        geometry, adopt the token path into the radix index
        (serve/pages.PrefixCache.adopt), and copy only the pages the
        index did not already hold into the device pool. → install
        summary dict, or None when the pool cannot host the missing
        pages (the caller degrades to a local prefill — never a torn
        page set).

        Raises serve/disagg.PageWireError(shape_mismatch) when the
        frame's geometry or dtype disagrees with this engine — a
        fleet mixing engine configs must fail loudly, not dequantize
        garbage — and PageWireError(model_skew) when both sides carry
        a lifecycle version and they differ: during a one-at-a-time
        /reloadz roll the fleet briefly serves two model versions, and
        KV prefilled under the other one must not be adopted here. Installed pages enter the index CACHED, so the next
        local admission maps them as an ordinary prefix hit — the
        decode stream is then the same continuation-program replay a
        local hit takes, which is what makes migrated streams
        token-identical to a hybrid replica (pinned by
        tests/test_disagg.py).
        """
        from ddp_tpu.serve.disagg import (
            MODEL_SKEW,
            SHAPE_MISMATCH,
            PageWireError,
        )

        if self.recurrent or self._dsa_rows:
            why = RECURRENT_REFUSALS if self.recurrent else LATENT_REFUSALS
            raise PageWireError(
                SHAPE_MISMATCH,
                f"install_prefix does not apply to the {self.spec.block} block: "
                f"{why['install_prefix']}",
            )
        if not self.paged:
            raise PageWireError(
                SHAPE_MISMATCH, "this engine is not paged (--page_size)"
            )
        if (
            frame.model_version is not None
            and self.model_version is not None
            and frame.model_version != self.model_version
        ):
            raise PageWireError(
                MODEL_SKEW,
                f"frame from {frame.model_version}, this replica "
                f"serves {self.model_version}",
            )
        quant = self._cache.quantized()
        depth, _, ps, h_kv, d_head = self._cache.k.shape
        want_dtype = "int8" if quant else "fp32"
        if frame.page_size != ps:
            raise PageWireError(
                SHAPE_MISMATCH,
                f"frame page_size {frame.page_size} != pool {ps}",
            )
        if frame.dtype != want_dtype:
            raise PageWireError(
                SHAPE_MISMATCH,
                f"frame dtype {frame.dtype} != pool {want_dtype}",
            )
        if frame.k.shape[0] != depth or frame.k.shape[3:] != (
            h_kv, d_head,
        ):
            raise PageWireError(
                SHAPE_MISMATCH,
                f"frame kv {frame.k.shape} != pool "
                f"[{depth}, ·, {ps}, {h_kv}, {d_head}]",
            )
        got = self._prefix.adopt(frame.tokens)
        if got is None:
            return None
        pids, fill = got
        cache = self._cache
        for ordinal, pid in fill:
            # One eager dynamic-index scatter per page: the page id is
            # a traced scalar, so every install reuses ONE compiled
            # update per array — migrations never grow the program
            # set (the bounded-compile pin holds).
            i = jnp.int32(pid)
            cache = cache._replace(
                k=cache.k.at[:, i].set(jnp.asarray(frame.k[:, ordinal])),
                v=cache.v.at[:, i].set(jnp.asarray(frame.v[:, ordinal])),
            )
            if quant:
                cache = cache._replace(
                    k_scale=cache.k_scale.at[:, i].set(
                        jnp.asarray(frame.k_scale[:, ordinal])
                    ),
                    v_scale=cache.v_scale.at[:, i].set(
                        jnp.asarray(frame.v_scale[:, ordinal])
                    ),
                )
        self._cache = cache
        return {
            "pages": len(pids),
            "copied_pages": len(fill),
            "tokens": len(pids) * self.page_size,
        }

    def block_stats(self) -> dict:
        """The block-diffusion counters (``/stats``'s
        ``block_diffusion``, ``/metricsz``'s ``ddp_tpu_serve_block_*``
        and ``ddp_tpu_serve_moe_*``); plain host ints, readable
        without the server's lock."""
        return {
            "block_length": self.block_len,
            "denoise_steps": self.spec.denoise_steps,
            "unmask": self.spec.unmask,
            "block_forwards_total": self.block_forwards_total,
            "blocks_committed_total": self.blocks_committed_total,
            "tokens_committed_total": self.tokens_committed_total,
            "positions_unmasked_total": self.positions_unmasked_total,
            "moe_tokens_routed_total": self.moe_tokens_routed_total,
            "moe_expert_load_max": self.moe_expert_load_max,
            "moe_expert_load_max_sum": self.moe_expert_load_max_sum,
            "moe_experts_hit_total": self.moe_experts_hit_total,
            "moe_layer_calls_total": self.moe_layer_calls_total,
        }

    def spec_acceptance_rate(self) -> Optional[float]:
        """Lifetime draft-acceptance fraction, None before any verify
        round (or when speculation is off)."""
        if not self.spec_drafted_total:
            return None
        return self.spec_accepted_total / self.spec_drafted_total

    def queue_drain_eta_s(self) -> Optional[float]:
        """Estimated seconds until the CURRENT queue drains, from the
        recent retirement rate (``drain_eta_s`` over the bounded
        retire-time window). None before two retirements exist — the
        caller falls back to a static Retry-After then. This is what a
        backpressure (queue_full) rejection advertises: "come back
        when a seat should be free", not a constant."""
        return drain_eta_s(
            list(self._retire_times), self.scheduler.depth
        )

    def goodput(self) -> dict:
        """Device-busy seconds over wall seconds since engine start."""
        wall = self.clock() - self._started_at
        return {
            "productive_s": round(self._productive_s, 4),
            "wall_s": round(wall, 4),
            "goodput": (
                round(self._productive_s / wall, 6) if wall > 0 else None
            ),
        }

    def stats(
        self, *, include_ledger: bool = False,
        include_states: bool = False,
    ) -> dict:
        """JSON-ready operational snapshot (the /stats endpoint).

        ``include_ledger`` embeds the full per-executable compile
        ledger; the default keeps the snapshot scalar-cheap — the
        /metricsz renderer only reads the gauge fields, and a
        Prometheus scrape must not pay a per-profile dict copy (which
        grows with the ledger) for three gauges. ``include_states``
        embeds the latency summaries' full mergeable StatSummary
        states (reservoir included) — what /statusz serves so the
        fleet aggregator (obs/aggregate.py) can merge EXACTLY; off by
        default for the same scrape-cost reason.
        """
        return {
            "slots": self.num_slots,
            "active": self.active,
            "queue_depth": self.scheduler.depth,
            "steps": self._steps,
            "completed": len(self._completed),
            "tokens_total": self.tokens_emitted_total,
            "accepted_total": self.accepted_total,
            "kv_rows_attended_total": self.kv_rows_attended_total,
            "kv_rows_lane_total": self.kv_rows_lane_total,
            "ttft_s": self.ttft.snapshot(),
            "lock_wait_s": self.lock_wait.snapshot(ndigits=6),
            "pickup_s": self.pickup.snapshot(ndigits=6),
            "tpot_s": self.tpot.snapshot(ndigits=6),
            "queue_s": self.queue_wait.snapshot(ndigits=6),
            "decode_tokens_per_s": self.decode_rate.snapshot(),
            "step_latency_s": self.step_latency.snapshot(ndigits=6),
            "rejects": dict(self.reject_counts),
            "requests_by_status": dict(self.status_counts),
            "build_info": dict(self._build_info),
            **(
                {
                    "summary_states": {
                        "ttft_s": self.ttft.to_state(),
                        "tpot_s": self.tpot.to_state(),
                        "queue_s": self.queue_wait.to_state(),
                        "decode_tokens_per_s":
                            self.decode_rate.to_state(),
                    }
                }
                if include_states
                else {}
            ),
            # Block diffusion: absent on a one-token model, whose
            # stats stay byte-identical.
            **(
                {"block_diffusion": self.block_stats()}
                if self.block_len else {}
            ),
            # Recurrent lanes: absent where no lane holds such state.
            **(
                {"recurrent_state": self.recurrent_stats()}
                if self.recurrent else {}
            ),
            # Latent lanes whose keys are selected: absent elsewhere.
            **(
                {"latent_attention": self.latent_stats()}
                if self._dsa_rows else {}
            ),
            # Paged KV + prefix index (PR 12): absent on fixed-lane
            # engines, so the default /metricsz exposition stays
            # byte-identical to the pre-paging engine's.
            **(
                {"paged": self.page_stats()} if self.paged else {}
            ),
            # Model lifecycle (serve/lifecycle.py): absent until a
            # version label is set or a swap/rollback has happened —
            # a pre-lifecycle engine's stats stay byte-identical.
            **(
                {
                    "lifecycle": {
                        **(
                            {"model_version": self.model_version}
                            if self.model_version is not None
                            else {}
                        ),
                        "reloads_total": self.reloads_total,
                        "rollbacks_total": self.rollbacks_total,
                    }
                }
                if self.model_version is not None
                or self.reloads_total
                or self.rollbacks_total
                else {}
            ),
            # SLO + request-trace state render only when configured:
            # with both off the /metricsz exposition stays
            # byte-identical to the pre-SLO engine's (the PR-2/PR-9
            # disabled-pin convention; pinned by tests/test_slo.py).
            **(
                {"slo": self._slo.state()}
                if self._slo is not None
                else {}
            ),
            **(
                {
                    "reqtrace": {
                        "live": self._reqtrace.live_count,
                        "retained": self._reqtrace.retired_count,
                        # Fleet adoption counters render only once a
                        # trace context has actually arrived, so a
                        # classic single-process engine's stats stay
                        # byte-identical.
                        **(
                            {
                                "propagated": self.trace_propagated,
                                "orphaned": self.trace_orphaned,
                            }
                            if self.trace_propagated
                            or self.trace_orphaned
                            else {}
                        ),
                    }
                }
                if self._reqtrace is not None
                else {}
            ),
            "compile_counts": self.compile_counts(),
            "prefill": {
                "chunk": self.prefill_chunk,
                "min_bucket": self.min_bucket,
                "buckets": list(self.buckets),
                "step_token_budget": self.step_token_budget,
                "admit_every": self.admit_every,
            },
            "decode_path": {
                "attn_impl": self.decode_attn,
                "kv_dtype": self.kv_dtype,
                "cache_bytes_per_slot": self.cache_bytes_per_slot(),
                "spec_tokens": self.spec_tokens,
                **(
                    {
                        "spec_acceptance": self.spec_acceptance_rate(),
                        "spec_drafted_total": self.spec_drafted_total,
                        "spec_accepted_total": self.spec_accepted_total,
                        # Per-request distribution (the lifetime ratio
                        # above hides stragglers: one cold request in
                        # a warm fleet shows up here).
                        "spec_acceptance_per_request":
                            self.accept_rate.snapshot(),
                    }
                    if self.spec_tokens
                    else {}
                ),
            },
            "goodput": self.goodput(),
            # Compiled-program introspection, only when instrumented:
            # an xprof-less engine's stats (and its /metricsz
            # rendering) stay byte-identical.
            **(
                {
                    "xprof": {
                        "programs": self._xprof.program_count,
                        "compile_s_total": round(
                            self._xprof.total_compile_s, 4
                        ),
                        **(
                            {"ledger": self._xprof.ledger_records()}
                            if include_ledger
                            else {}
                        ),
                        "hbm": self._hbm.sample(),
                    }
                }
                if self._xprof.enabled
                else {}
            ),
        }

    # ---- engine loop ------------------------------------------------

    def step(self) -> int:
        """One engine iteration → number of tokens scheduled.

        Order: (1) retire finished / evict expired requests (a
        finished lane whose last values are still in flight waits one
        step for them while other lanes decode; otherwise the values
        it is owed are drained here), (2) evict expired
        queued requests, (3) admit queue heads into free slots, (4)
        dispatch prefill chunks within the step token budget — a slot
        whose FINAL chunk lands this step joins the decode batch
        immediately (continuous batching, no drain barrier), (5)
        dispatch one fused decode+sample step over all slots, (6)
        retire the PREVIOUS step's [S] int32 token vector — the only
        steady-state device→host transfer — while the device computes
        what was just dispatched.

        Spans (obs/tracer.py, always on): ``serve.step`` over all of
        it, and as its children ``serve.retire`` (1-2), ``serve.admit``
        (3 and the chunk plan), one ``serve.prefill_chunk`` per chunk
        and ``serve.decode`` (the host's dispatch only) and
        ``serve.sample`` (6, the wait for the device). None of them
        syncs: tracing leaves the one-step-behind overlap as it is.
        """
        with self.tracer.span("serve.step") as span:
            produced = self._step(span.t0)
            span.nums = (produced, self.active, self.scheduler.depth)
        return produced

    def _step(self, parent: float) -> int:
        tracer = self.tracer
        now = self.clock()
        t_step = time.perf_counter()
        evictions = 0
        with tracer.span("serve.retire", parent=parent) as span:
            finished = 0
            # Lanes that still owe tokens: while there are any, a lane
            # whose LAST values are in flight (dispatched by the step
            # before this one) is retired a step later, when this
            # step's own lagged fetch has brought them. Fetching them
            # here would wait for the device with nothing queued behind
            # it, and the device would then idle through this step's
            # retire, admit and dispatch.
            owing = sum(
                1 for s in self._slots
                if s.request is not None
                and s.emitted < s.request.max_new_tokens
            )
            for slot in self._slots:
                req = slot.request
                if req is None:
                    continue
                if slot.emitted >= req.max_new_tokens:
                    # the completion needs its token values (in a
                    # block they are in hand: commits are counted as
                    # they are fetched, so nothing waits)
                    if not self.block_len:
                        if owing and len(slot.tokens) < slot.emitted:
                            continue
                        self._drain(parent=span.t0)
                    self._finish(slot, COMPLETE)
                    finished += 1
                elif req.expired(now):
                    self._drain(parent=span.t0)
                    self._finish(slot, TIMEOUT_EVICTED)
                    evictions += 1
            for req in self.scheduler.evict_expired():
                now2 = self.clock()
                c = Completion(
                    rid=req.rid, status=TIMEOUT_QUEUE, prompt=req.prompt,
                    tokens=[], ttft=None, decode_seconds=0.0,
                    submitted=req.submitted, finished=now2,
                )
                self._completed[req.rid] = c
                self._retire_trace(c)
                self._record_request(c)
                evictions += 1
            span.nums = (finished + evictions,)

        with tracer.span("serve.admit", parent=parent) as span:
            admitted = 0
            for slot in self._slots:
                # Admission pause (hot-swap barrier): running lanes
                # keep decoding above; queue heads stay queued until
                # the swap commits or rolls back.
                if self._admission_paused:
                    break
                if not slot.free or self.scheduler.depth == 0:
                    continue
                # Spaced admission (``admit_every``): an idle engine
                # takes its first request at once.
                if (
                    self.admit_every
                    and self._last_admit_step is not None
                    and self._steps - self._last_admit_step
                    < self.admit_every
                    and self.active > 0
                ):
                    break
                req = self.scheduler.next_request()
                if req is None:
                    break
                outcome = self._admit_to_slot(slot, req)
                if outcome == "starved":
                    # Page-starved bind: _admit_to_slot put the FIFO
                    # head back at the queue front — stop admitting
                    # (later requests must not overtake it) and retry
                    # after retirements free pages.
                    break
                if outcome == "bound":
                    admitted += 1
                    self._last_admit_step = self._steps

            # Paged mode: page-table mutations (binds above, retires
            # at the top of this step) upload ONCE here, before any
            # dispatch — one [S, lane_pages] int32 host→device copy
            # per mutating step, nothing on the steady-state path.
            if self.paged and self._table_dirty:
                self._cache = self._cache._replace(
                    table=self._put(self._table_np)
                )
                self._table_dirty = False

            prefilling = [
                (i, s.prefill_pos, s.prefill_goal - s.prefill_pos)
                for i, s in enumerate(self._slots)
                if s.prefilling
            ]
            # plan_chunks' FIFO contract is ADMISSION order, not
            # slot-index order: under a tight budget the head gets
            # full width and followers shrink/defer, so a newer
            # request refilled into a lower-index lane must not starve
            # an older one's prefill.
            prefilling.sort(key=lambda t: self._slots[t[0]].request.rid)
            decode_lanes = [
                i for i, s in enumerate(self._slots) if s.decoding
            ]
            # Budget accounting: a decoding lane costs
            # tokens_per_decode budget tokens this step — 1 on the
            # plain path, γ under speculation (the verify program runs
            # γ positions per lane).
            plan = self.scheduler.plan_chunks(
                prefilling, len(decode_lanes) * self._tokens_per_decode
            )
            span.nums = (admitted, len(plan))

        # Everything below is device dispatch + the one-step-lagged
        # retirement; anything fetched in (6) was dispatched LAST step
        # and has been computing since.
        prev_pending = self._pending
        self._pending = []
        self._step_spec = (0, 0)  # (drafted, accepted) this step
        produced = 0
        tokens_before = self.tokens_emitted_total
        w0 = self.clock()
        t_dispatch = time.perf_counter()
        device_work = False
        chunk_tokens = 0
        for i, width in plan:
            slot = self._slots[i]
            req = slot.request
            start = slot.prefill_pos
            live = min(width, slot.prefill_goal - start)
            final = start + live == slot.prefill_goal
            with tracer.span(
                "serve.prefill_chunk", parent=parent,
                nums=(req.rid, i, start, width, int(final)),
            ) as span:
                buf = np.zeros((width,), np.int32)
                buf[:live] = req.prompt[start : start + live]
                # First chunk: self-contained causal attention (the chunk
                # IS its own causal prefix at start == 0) — short prompts
                # never pay a total_len-wide lane read. Continuations
                # attend the full lane under the banded q_offset mask.
                fn = self._chunk_first if start == 0 else self._chunk_cont
                # The chunk's scalars go in as NUMPY values, uploaded
                # with the call: ``jnp.int32(x)`` is a device program of
                # its own (``jit_convert_element_type``), a host dispatch
                # each, and a chunk that follows a retirement's full
                # drain is dispatched with the device idle behind it.
                slot_i, tok_buf = np.int32(i), buf
                start_t, live_t = np.int32(start), np.int32(live)
                final_t = np.bool_(final)
                # Exact int32 seed (admission range-checks it): any
                # masking here would break token-identity with
                # generate(seed=...) for negative seeds.
                sampling = (
                    np.int32(req.seed),
                    np.float32(req.temperature), np.float32(req.top_p),
                )
                if self.block_len:
                    # The final chunk installs the lane's first block:
                    # the prompt's last len % B tokens open it, and
                    # what the request is owed rides along. It owes no
                    # token; its routing counts are fetched a step
                    # behind like everything else.
                    tail = np.zeros((self.block_len,), np.int32)
                    tail[: slot.lead] = req.prompt[slot.prefill_goal :]
                    self._cache, self._lanes, moe = fn(
                        self.params, self._cache, self._lanes,
                        slot_i, tok_buf, start_t, live_t, final_t,
                        tail, np.int32(slot.lead),
                        np.int32(req.max_new_tokens), *sampling,
                    )
                    self._pending.append(("moe", moe, None))
                    first = None
                else:
                    (self._cache, self._toks, self._seeds,
                     self._sample_steps, self._temps, self._top_ps,
                     first, *pairs) = fn(
                        self.params, self._cache, self._toks, self._seeds,
                        self._sample_steps, self._temps, self._top_ps,
                        slot_i, tok_buf, start_t, live_t, final_t,
                        *sampling,
                    )
                    self._pending += [("pairs", p, None) for p in pairs]
                if self.spec_tokens:
                    # The draft cache tracks the same token history: the
                    # same chunk ingests into its lane (never final — the
                    # request's first token is the TARGET's draw; the
                    # draft's dummy sampling state is never read).
                    dfn = (
                        self._draft_chunk_first
                        if start == 0
                        else self._draft_chunk_cont
                    )
                    (self._draft_cache, self._d_toks, self._d_seeds,
                     self._d_steps, self._d_temps, self._d_top_ps, _) = dfn(
                        self.draft_params, self._draft_cache, self._d_toks,
                        self._d_seeds, self._d_steps, self._d_temps,
                        self._d_top_ps,
                        slot_i, tok_buf, start_t, live_t, self._keep_pos,
                        jnp.int32(req.seed),
                        jnp.float32(req.temperature),
                        jnp.float32(req.top_p),
                    )
            t0 = span.t0
            chunk_dur = time.perf_counter() - t0
            device_work = True
            slot.prefill_pos = start + live
            chunk_tokens += live
            if self.recurrent:
                # a first chunk starts the lane's state from zero
                self.ssm_state_resets_total += start == 0
                self.ssm_prefill_tokens_total += live
            if self._attended_rows:
                # the final chunk's last position alone goes on through
                # the layers that write nothing into a lane
                self.prefill_self_positions_total += live
                self.prefill_cross_positions_total += bool(final)
            if self._dsa_rows:
                scored, selected = self._dsa_rows(self.spec, start, live)
                self.dsa_rows_scored_total += scored
                self.dsa_rows_selected_total += selected
                tracer.complete(
                    "serve.chunk_selected", time.perf_counter(), 0.0,
                    parent=parent,
                    nums=(scored, selected, live, start, int(final)),
                )
            if self._reqtrace is not None:
                tr = self._reqtrace.get(req.rid)
                if tr is not None:
                    tr.prefill_chunk(
                        t0, chunk_dur, start=start, bucket=width,
                        tokens=live, final=final,
                    )
            if final and self.block_len:
                slot.installed = True
                decode_lanes.append(i)
            elif final:
                slot.emitted = 1
                produced += 1
                self._pending.append(("first", first, i))
                decode_lanes.append(i)

        emit_lanes = [
            i
            for i in decode_lanes
            if self._slots[i].emitted
            < self._slots[i].request.max_new_tokens
        ]
        # Dispatch only when some lane will actually emit: a step whose
        # every decoding lane already filled its budget (all retiring
        # next step) would compute a full [S, total_len] decode and
        # throw the entire output away.
        if emit_lanes and self.block_len:
            self._block_round(emit_lanes, parent)
            device_work = True
        elif emit_lanes and self.spec_tokens:
            produced += self._spec_round(emit_lanes, parent)
            device_work = True
        elif emit_lanes:
            # --sanitize: every steady-state decode input is already
            # device-resident, so the guard proves this dispatch does
            # ZERO implicit host transfer — the PR-3 invariant,
            # enforced instead of assumed. (Chunk dispatch above
            # legitimately uploads prompt content; the retire below
            # legitimately fetches [S] int32 — both deliberate.)
            # A decoding lane writes its last token at pos = prompt +
            # emitted - 1 and attends the pos + 1 rows up to it.
            rows = sum(
                len(self._slots[i].request.prompt) + self._slots[i].emitted
                for i in decode_lanes
            )
            self.kv_rows_attended_total += rows
            self.kv_rows_lane_total += self.num_slots * self.spec.total_len
            if self._attended_rows:
                # over the LIVE lanes: a lane that has its last token
                # and waits to be retired is not advanced
                ring, shared = self._attended_rows(self.spec, [
                    len(self._slots[i].request.prompt)
                    + self._slots[i].emitted for i in emit_lanes
                ])
                self.kv_ring_rows_attended_total += ring
                self.kv_shared_rows_attended_total += shared
            if self._dsa_rows:
                # over the LIVE lanes, as above: a lane at row count r
                # queries from position r - 1
                scored = selected = 0
                for i in emit_lanes:
                    a, b = self._dsa_rows(
                        self.spec, len(self._slots[i].request.prompt)
                        + self._slots[i].emitted - 1, 1)
                    scored, selected = scored + a, selected + b
                self.dsa_rows_scored_total += scored
                self.dsa_rows_selected_total += selected
            if self.recurrent:
                self._name_live_lanes(emit_lanes)
            with tracer.span(
                "serve.decode", parent=parent,
                nums=(len(decode_lanes), rows),
            ) as span, self._sanitizer.guard():
                (self._toks, self._cache, self._sample_steps,
                 *pairs) = self._decode(
                    self.params, self._cache, self._toks, self._seeds,
                    self._sample_steps, self._temps, self._top_ps,
                )
            self._pending += [("pairs", p, None) for p in pairs]
            t0 = span.t0
            if self._attended_rows:
                tracer.complete(
                    "serve.decode_rows", time.perf_counter(), 0.0,
                    parent=parent, nums=(ring, shared, len(emit_lanes)),
                )
            if self._dsa_rows:
                tracer.complete(
                    "serve.decode_selected", time.perf_counter(), 0.0,
                    parent=parent,
                    nums=(scored, selected, len(emit_lanes)),
                )
                # what this step selected, for each lane that asked and
                # whose LAST owed token it produced (a lane's later
                # steps, owed nothing, would overwrite it): kept on the
                # device until the request finishes
                for i in emit_lanes:
                    slot = self._slots[i]
                    if (slot.request.record_selection and slot.emitted + 1
                            == slot.request.max_new_tokens):
                        slot.selection = self._lane_selection(
                            self._cache.sel, np.int32(i))
            device_work = True
            for i in emit_lanes:
                self._slots[i].emitted += 1
                if self._reqtrace is not None:
                    # Aggregate decode accounting: one counter bump
                    # per lane per step, folded into ONE req.decode
                    # span at retire — never an event per token.
                    tr = self._reqtrace.get(self._slots[i].request.rid)
                    if tr is not None:
                        tr.decode_step(t0)
            self._pending.append(("decode", self._toks, emit_lanes))
            produced += len(emit_lanes)

        dispatch_s = time.perf_counter() - t_dispatch
        t_retire = time.perf_counter()
        drained = self._drain(prev_pending, parent=parent)
        retire_s = time.perf_counter() - t_retire
        if device_work or drained:
            self._productive_s += self.clock() - w0

        self._steps += 1
        if self.block_len:
            # Committed tokens were counted as they were fetched
            # (``_drain``): a forward yields none of its own.
            produced = self.tokens_emitted_total - tokens_before
        else:
            self.tokens_emitted_total += produced
        self.step_latency.add(time.perf_counter() - t_step)
        # Speculative rounds report their per-step acceptance in the
        # serve_step stream (the ISSUE-10 contract); non-speculative
        # engines keep the record schema byte-identical.
        spec_fields = {}
        if self.spec_tokens:
            drafted, accepted = self._step_spec
            spec_fields = dict(
                spec_drafted=drafted,
                spec_accepted=accepted,
                spec_acceptance=(
                    round(accepted / drafted, 4) if drafted else None
                ),
            )
        if self.paged:
            # Paged gauges ride the step stream (health_report's
            # page/prefix triage line keys on their presence);
            # fixed-lane engines keep the serve_step schema
            # byte-identical.
            p = self._prefix
            spec_fields.update(
                pages_free=p.free_pages,
                pages_resident=p.resident_pages,
                pages_shared=p.shared_pages,
                prefix_hit_rate=(
                    round(p.hit_rate(), 4)
                    if p.hit_rate() is not None else None
                ),
            )
        self.metrics.write(
            "serve_step",
            step=self._steps,
            queue_depth=self.scheduler.depth,
            active_slots=self.active,
            slot_occupancy=round(self.active / self.num_slots, 4),
            evictions=evictions,
            tokens=produced,
            prefill_chunk_tokens=chunk_tokens,
            dispatch_s=round(dispatch_s, 6),
            retire_s=round(retire_s, 6),
            **spec_fields,
        )
        return produced

    def run(self, *, max_steps: Optional[int] = None) -> list[Completion]:
        """Drive ``step()`` until idle (or ``max_steps``) → completions
        retired during this call, in finish order."""
        before = set(self._completed)
        steps = 0
        # A request whose budget fills on a decode step retires at the
        # START of the next step, so ``pending`` stays true until the
        # retire pass has run — no trailing flush needed.
        while self.pending and (max_steps is None or steps < max_steps):
            self.step()
            steps += 1
        return sorted(
            (c for r, c in self._completed.items() if r not in before),
            key=lambda c: c.finished,
        )

    # ---- internals --------------------------------------------------

    def _name_live_lanes(self, lanes: list[int]) -> None:
        """Tell the device which lanes the coming decode step advances
        (``cache.live``): one ``[S]`` bool upload when the set differs
        from the last step's — a lane joined after its final chunk, or
        finished — and nothing on a steady step. Every other lane's
        recurrent state, tail and position are left as they are: a lane
        between two chunks of its prompt carries its state on, an idle
        one costs no state traffic."""
        self.ssm_lane_updates_total += len(lanes)
        named = tuple(lanes)
        if named != self._live_lanes:
            mask = np.zeros((self.num_slots,), bool)
            mask[lanes] = True
            self._cache = self._cache._replace(live=self._put(mask))
            self._live_lanes = named

    def _block_round(self, lanes: list[int], parent: float) -> None:
        """One forward over every lane's block (``sdar.block_step``),
        dispatched and left: what it did — which lanes were
        generating, which committed their block and with which tokens,
        how many positions were unmasked — comes back in ONE
        ``[S, 2B + 3]`` int32 report, fetched a step behind like the
        decode step's token vector, so the steps of a block cost no
        host round trip. ``lanes`` are the lanes the host holds to be
        in a block; each rides along with its request's id, because by
        the time the report is read the lane may have been retired and
        bound again. Counts (forwards, commits, tokens) are taken from
        the report: the device stops a lane the moment its request is
        paid off, a step before the host can know.
        """
        unmasked, committed = self._last_round
        with self.tracer.span(
            "serve.block_step", parent=parent,
            nums=(len(lanes), unmasked, committed),
        ), self._sanitizer.guard():
            self._cache, self._lanes, report, moe = self._decode(
                self.params, self._cache, self._lanes
            )
        self._pending.append((
            "block", (report, moe),
            [(i, self._slots[i].request.rid) for i in lanes],
        ))

    def _read_block_report(self, report, lanes) -> int:
        """Book one fetched block-step report -> tokens committed."""
        B = self.block_len
        appended = unmasked = committed = 0
        for i, rid in lanes:
            slot = self._slots[i]
            req = slot.request
            row = report[i]
            # after the block's tokens and mask: sdar.REPORT_EXTRA
            was_active, did_commit, n_unmasked = row[2 * B : 2 * B + 3]
            if req is None or req.rid != rid or not was_active:
                continue
            self.block_forwards_total += 1
            unmasked += int(n_unmasked)
            if slot.block_log is not None:
                slot.block_log.append((
                    slot.block_pos, row[:B].tolist(),
                    row[B : 2 * B].tolist(),
                ))
            if not did_commit:
                continue
            committed += 1
            new = row[slot.lead : B].tolist()
            new = new[: req.max_new_tokens - slot.emitted]
            slot.tokens.extend(new)
            slot.emitted += len(new)
            appended += len(new)
            slot.lead = 0
            slot.block_pos += B
            if slot.first_token_at is None:
                slot.first_token_at = self.clock()
                slot.first_tokens = len(new)
                self.ttft.add(slot.first_token_at - req.submitted)
        self.blocks_committed_total += committed
        self.positions_unmasked_total += unmasked
        self.tokens_committed_total += appended
        self.tokens_emitted_total += appended
        self._last_round = (unmasked, committed)
        return appended

    def _spec_round(self, emit_lanes: list[int], parent: float) -> int:
        """One speculative round: γ draft proposals + one batched
        verify → tokens emitted (1..γ per lane).

        The draft model proposes γ greedy tokens per lane (its first
        step adopts the TARGET's per-lane positions — acceptance may
        have advanced the target less than the draft last round);
        ``slot_verify_step`` scores all of them in ONE target-model
        step and emits each lane's accepted prefix plus the target's
        correction token. The verify outputs' emit counts are
        data-dependent, so the host reads them at the END of the same
        step (two small int32 arrays — [S, γ] target tokens and [S]
        match counts, still never logits): spec mode trades the
        one-step retirement lag for up to γ tokens per big-model
        step. Runs fully under the --sanitize transfer guard up to
        that deliberate fetch. ``serve.spec_verify`` spans the round,
        that fetch included (its wait is the round's own).
        """
        gamma = self.spec_tokens
        # First-token scalars from THIS step's final chunks must land
        # before the verify tokens (slot.tokens is in stream order).
        self._drain(parent=parent)
        with self.tracer.span("serve.spec_verify", parent=parent) as span:
            t0 = span.t0
            with self._sanitizer.guard():
                t = self._toks
                pos0 = self._cache.pos
                drafts = []
                for j in range(gamma):
                    t, self._draft_cache = self._draft_decode(
                        self.draft_params, self._draft_cache, t, pos0,
                        self._sync_pos if j == 0 else self._keep_pos,
                    )
                    drafts.append(t)
                (self._toks, self._cache, self._sample_steps, target,
                 matched) = self._verify(
                    self.params, self._cache, self._toks,
                    jnp.stack(drafts, axis=1),
                    self._seeds, self._sample_steps, self._temps,
                    self._top_ps,
                )
            t_np = np.asarray(target)  # [S, γ] int32
            m_np = np.asarray(matched)  # [S] int32
            round_dur = time.perf_counter() - t0
            produced = 0
            drafted = accepted = 0
            for i in emit_lanes:
                slot = self._slots[i]
                m = int(m_np[i])
                n = min(
                    m + 1, gamma,
                    slot.request.max_new_tokens - slot.emitted,
                )
                slot.tokens.extend(int(x) for x in t_np[i, :n])
                slot.emitted += n
                produced += n
                drafted += gamma
                accepted += m
                slot.spec_drafted += gamma
                slot.spec_accepted += m
                if self._reqtrace is not None:
                    tr = self._reqtrace.get(slot.request.rid)
                    if tr is not None:
                        tr.spec_round(
                            t0, round_dur, drafted=gamma, accepted=m,
                            emitted=n,
                        )
            self.spec_drafted_total += drafted
            self.spec_accepted_total += accepted
            self._step_spec = (drafted, accepted)
            span.nums = (len(emit_lanes), drafted, accepted)
        return produced

    def _admit_to_slot(self, slot: _Slot, req: Request) -> str:
        """Bind a popped request to a lane → "bound" | "rejected" |
        "starved".

        "rejected": the belt to admission's braces — a prompt that
        cannot be served (longer than the admission ceiling, or
        leaving no room to decode) but slipped past the front door —
        a mutated scheduler config, a future code path — completes
        as REJECTED_TOO_LONG here rather than surfacing as a cryptic
        shape error from the middle of a jitted program. "starved"
        (paged only): the page pool could not satisfy the request's
        demand — it went back to the queue FRONT and the caller must
        stop admitting this step.
        """
        if len(req.prompt) > min(self.prefill_len, self.spec.total_len - 1):
            now = self.clock()
            c = Completion(
                rid=req.rid, status=REJECTED_TOO_LONG, prompt=req.prompt,
                tokens=[], ttft=None, decode_seconds=0.0,
                submitted=req.submitted, finished=now,
            )
            self._completed[req.rid] = c
            self._retire_trace(c)
            self._record_request(c)
            return "rejected"
        matched = 0
        pids: list[int] = []
        if self.paged:
            # Page-based admission (the lanes×ctx_len ceiling's
            # replacement): the lane must own every page it could
            # write — prompt + decode budget + the speculative γ-1
            # write reserve, in PAGES (serve/pages.page_demand), so a
            # verify round can never scatter into an unowned page.
            got = self._prefix.acquire(
                req.prompt,
                page_demand(
                    len(req.prompt), req.max_new_tokens,
                    self.page_size,
                    total_len=self.spec.total_len,
                    reserve=self.spec_tokens - 1
                    if self.spec_tokens else 0,
                ),
            )
            if got is None:
                # Pool exhausted even after LRU eviction: requeue at
                # the FRONT (FIFO order intact) and let the caller
                # stop admitting until retirements free pages.
                self.page_starved_binds += 1
                self.scheduler.push_front(req)
                return "starved"
            pids, matched = got
        slot.request = req
        slot.tokens = []
        slot.emitted = 0
        slot.prefill_pos = matched
        slot.first_token_at = None
        slot.spec_drafted = 0
        slot.spec_accepted = 0
        slot.pages = pids
        slot.matched_tokens = matched
        if self.block_len:
            # The prompt's whole blocks are prefilled; its last
            # len % B tokens open the first generated block.
            B = self.block_len
            slot.prefill_target = len(req.prompt) // B * B
            slot.installed = False
            slot.lead = len(req.prompt) - slot.prefill_target
            slot.block_pos = slot.prefill_target
            slot.block_log = [] if req.record_blocks else None
        if self.paged:
            self._table_np[slot.index] = 0
            self._table_np[slot.index, : len(pids)] = pids
            self._table_dirty = True
            # Position FLOOR at the matched-prefix length, applied on
            # device before any dispatch: idle-shape decode steps
            # write garbage at each lane's pos between chunks, and
            # pos >= matched at all times is exactly the invariant
            # that keeps those writes out of SHARED prefix pages
            # (private pages tolerate them — every line is rewritten
            # by its covering chunk or decode step before it becomes
            # attendable, the PR-3 invariant). A hit also skips the
            # matched pages' prefill outright: the first tail chunk
            # starts at ``matched`` through the continuation program.
            self._cache = self._cache._replace(
                pos=self._cache.pos.at[jnp.asarray(slot.index)].set(
                    jnp.int32(matched)
                )
            )
        # Queue wait closes here: the SLI behind queue_s_p99 and the
        # req.queue span (the bind is already a host-side touch point).
        slot.queue_s = max(0.0, self.clock() - req.submitted)
        self.queue_wait.add(slot.queue_s)
        if self._reqtrace is not None:
            tr = self._reqtrace.get(req.rid)
            if tr is not None:
                tr.bind(self._reqtrace.clock())
        # Sampling config reaches the device with the request's first
        # chunk (prefill_chunk installs it at the lane) — nothing to
        # upload here.
        return "bound"

    def _drain(
        self, items: Optional[list] = None, *,
        parent: Optional[float] = None,
    ) -> int:
        """Fetch dispatched-but-unread token values → tokens appended.

        The steady-state host sync: each item is either the previous
        decode step's [S] int32 vector or a final chunk's first-token
        scalar — never logits. Called with the previous step's items
        after this step's dispatches (the fetch overlaps the device
        computing the new work), and with everything outstanding when
        a retirement needs its values now. The one place the engine
        waits for the device: ``serve.sample`` is a WAIT span
        (obs/tracer.WAIT_SPANS), host time that is not host work.
        """
        if items is None:
            items = self._pending
            self._pending = []
        if not items:
            return 0
        with self.tracer.span("serve.sample", parent=parent) as span:
            appended = 0
            for kind, arr, meta in items:
                if kind == "pairs":
                    # The call's (expert, token) pairs: routed, and
                    # those whose expert is held here.
                    routed, held = (int(x) for x in np.asarray(arr))
                    self.moe_pairs_routed_total += routed
                    self.moe_pairs_held_total += held
                    continue
                if kind in ("block", "moe"):
                    # The expert layer's routing counts of the call
                    # (rows, fullest expert summed over layers,
                    # experts hit), and a block step's report.
                    moe = arr[1] if kind == "block" else arr
                    rows, load, hit = (int(x) for x in np.asarray(moe))
                    self.moe_tokens_routed_total += rows
                    self.moe_expert_load_max_sum += load
                    self.moe_experts_hit_total += hit
                    self.moe_layer_calls_total += self.spec.depth
                    if kind == "block":
                        self.moe_expert_load_max = load // self.spec.depth
                        appended += self._read_block_report(
                            np.asarray(arr[0]), meta
                        )
                    continue
                vals = np.asarray(arr)
                if kind == "first":
                    slot = self._slots[meta]
                    slot.tokens.append(int(vals))
                    appended += 1
                    slot.first_token_at = self.clock()
                    if slot.request is not None:
                        self.ttft.add(
                            slot.first_token_at - slot.request.submitted
                        )
                else:
                    for i in meta:
                        self._slots[i].tokens.append(int(vals[i]))
                        appended += 1
            span.nums = (appended,)
        return appended

    def _finish(self, slot: _Slot, status: str) -> None:
        req = slot.request
        now = self.clock()
        first = slot.first_token_at  # None = evicted before any token
        c = Completion(
            rid=req.rid,
            status=status,
            prompt=req.prompt,
            tokens=list(slot.tokens),
            ttft=(first - req.submitted) if first is not None else None,
            decode_seconds=(now - first) if first is not None else 0.0,
            submitted=req.submitted,
            finished=now,
            # Per-completion acceptance (ISSUE-10): fraction of this
            # request's draft proposals the target accepted. None when
            # no verify round ran for it (spec off, or the request
            # finished on its prefill token alone).
            spec_acceptance=(
                round(slot.spec_accepted / slot.spec_drafted, 4)
                if slot.spec_drafted
                else None
            ),
            queue_s=slot.queue_s,
            prefix_hit_tokens=(
                slot.matched_tokens if self.paged else None
            ),
            first_tokens=slot.first_tokens,
            block_inputs=slot.block_log,
            selected_rows=(
                None if slot.selection is None
                else np.asarray(slot.selection).tolist()
            ),
        )
        self._completed[req.rid] = c
        self._retire_times.append(now)
        if len(c.tokens) > c.first_tokens:
            self.decode_rate.add(c.decode_tokens_per_s)
        if c.tpot_s is not None:
            self.tpot.add(c.tpot_s)
        if c.spec_acceptance is not None:
            self.accept_rate.add(c.spec_acceptance)
        self._retire_trace(c)
        self._record_request(c)
        if self.paged and slot.pages:
            # Publish the prompt's fully-prefilled pages into the
            # radix index (decode output stays private; prefill_pos
            # caps mid-prefill evictions) and unmap everything the
            # lane held — published pages go LRU-cached at refcount
            # 0, the rest return to the free list. The lane's table
            # row zeroes (→ the scratch page) so the idle-shape
            # decode can never write into freed/reallocated pages.
            self._prefix.release(
                req.prompt, slot.pages, slot.prefill_pos
            )
            self._table_np[slot.index] = 0
            self._table_dirty = True
        slot.request = None
        slot.tokens = []
        slot.emitted = 0
        slot.prefill_pos = 0
        slot.first_token_at = None
        slot.queue_s = None
        slot.spec_drafted = 0
        slot.spec_accepted = 0
        slot.pages = []
        slot.matched_tokens = 0
        slot.prefill_target = None
        slot.installed = True
        slot.lead = slot.block_pos = 0
        slot.first_tokens = 1
        slot.block_log = None
        slot.selection = None

    def _retire_trace(self, c: Completion) -> None:
        """Close the request's trace (if tracing) and hang the digest
        on the completion — called from every retirement path."""
        if self._reqtrace is None:
            return
        t = self._reqtrace.retire(c.rid, c.status, tracer=self.tracer)
        if t is not None:
            c.trace = t.summary()

    def _on_slo_breach(self, state: dict) -> None:
        """SLO alert transition (obs/slo.py multi-window burn): one
        record into the metrics stream AND the flight recorder ring
        (the PR-4 post-mortem artifact) per False→True transition,
        then the caller's hook if any."""
        fields = dict(
            objective=state["name"],
            target=state["target"],
            current=state["current"],
            burn_rate_fast=state["burn_rate_fast"],
            burn_rate_slow=state["burn_rate_slow"],
            window_n=state["window_n"],
        )
        self.metrics.write("slo_breach", **fields)
        self._recorder.record("slo_breach", **fields)
        if self._user_breach_cb is not None:
            self._user_breach_cb(state)

    def request_timeline(self, key) -> Optional[dict]:
        """One request's full event timeline by rid or hex trace id —
        the /requestz payload. None when unknown (or tracing off)."""
        if self._reqtrace is None:
            return None
        t = self._reqtrace.lookup(key)
        if t is None:
            return None
        doc = t.timeline()
        doc["live"] = t.retire_t is None
        return doc

    def emit_request_spans(self) -> int:
        """Retroactively emit retired request traces into the span
        tracer (→ count). The bench path: its timed window runs with
        the tracer's ``enabled`` level off and exports the request
        spans afterwards — stamps were recorded live, so the exported
        timeline is the measured one."""
        if self._reqtrace is None:
            return 0
        return self._reqtrace.emit_all(self.tracer)

    def _record_request(self, c: Completion) -> None:
        self.status_counts[c.status] = self.status_counts.get(c.status, 0) + 1
        fields = dict(
            rid=c.rid,
            status=c.status,
            prompt_len=len(c.prompt),
            new_tokens=len(c.tokens),
            decode_tokens_per_s=round(c.decode_tokens_per_s, 2),
        )
        # Requests that never produced a token carry no ttft_s at all:
        # downstream aggregation must see only real first-token
        # latencies, not queue-timeout wait times.
        if c.ttft is not None:
            fields["ttft_s"] = round(c.ttft, 4)
        # Same absent-vs-null contract for acceptance: only requests
        # that actually ran verify rounds carry the field.
        if c.spec_acceptance is not None:
            fields["spec_acceptance"] = c.spec_acceptance
        # The user-facing SLIs (absent when the request never bound a
        # lane / never decoded — aggregation sees only real values).
        if c.queue_s is not None:
            fields["queue_s"] = round(c.queue_s, 6)
        if c.tpot_s is not None:
            fields["tpot_s"] = round(c.tpot_s, 6)
        # Cross-plane correlation key: present only when request
        # tracing is on (the serve_request schema is otherwise
        # byte-compatible with the pre-reqtrace stream).
        if c.trace is not None:
            fields["trace_id"] = c.trace["trace_id"]
        # Prefix-reuse accounting, paged engines only (the bench's
        # TTFT hit-vs-miss split reads this).
        if c.prefix_hit_tokens is not None:
            fields["prefix_hit_tokens"] = c.prefix_hit_tokens
        # Which model served this request — only engines with a
        # version label carry it (pre-lifecycle streams unchanged);
        # stamped at retirement, so a request that straddled a swap is
        # attributed to the version that finished it.
        if self.model_version is not None:
            fields["model_version"] = self.model_version
        # Per-hop seconds (ISSUE 19): only requests the router staged
        # with a fleet trace carry the key — the router's queue/
        # handoff/migrate seconds joined with this engine's own
        # queue/decode split, so ONE record attributes the whole TTFT.
        router_hops = self._request_hops.pop(c.rid, None)
        if router_hops is not None:
            hops = dict(router_hops)
            if c.trace is not None:
                for k in ("queue_s", "prefill_s", "decode_s"):
                    if c.trace.get(k) is not None:
                        hops[f"engine_{k}"] = c.trace[k]
            fields["hops"] = hops
        self.metrics.write("serve_request", **fields)
        # Feed the SLO engine from the same retirement: the SLIs are
        # host floats already in hand, and availability counts every
        # SERVICE-terminal status (a timeout IS an unavailability
        # event). Client-class rejections are excluded — a burst of
        # over-long prompts must not burn the availability budget and
        # page the operator while valid traffic is served perfectly
        # (admission rejects never reach this path either).
        if self._slo is not None and c.status != REJECTED_TOO_LONG:
            self._slo.observe(
                ttft_s=c.ttft,
                tpot_s=c.tpot_s,
                queue_s=c.queue_s,
                ok=c.status == COMPLETE,
            )


imported(__name__, _IMPORT_T0)
