"""The qwen3_moe block and generation by masked diffusion over blocks
(models/sdar.py, ops/moe.py, the serve engine's block round) against
the plain reference ``benchmarks/reference/sdar_ref.py``, at a small
size on the CPU with seeded random float32 weights.

Tolerances. Program and reference compute the same float32 mathematics
in another order (grouped experts against every expert on every token,
a cache against a full forward, online softmax against a dense one), so
logits of magnitude ~5 agree to a few float32 roundings accumulated
over three layers: 2e-4 absolute, about forty times what is seen
(5e-6). The control — the same program with every matmul operand
rounded to float8 — misses by ~0.1-1 and has to FAIL that tolerance.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import sdar_ref as ref
from ddp_tpu.models import sdar
from ddp_tpu.models.generate import init_slot_cache
from ddp_tpu.models.lm import LMSpec
from ddp_tpu.ops import moe as moe_ops
from ddp_tpu.serve.engine import COMPLETE, TIMEOUT_EVICTED, ServeEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
MASK = 96

SPEC = LMSpec(
    vocab_size=97, total_len=64, d_model=64, depth=3, num_heads=8,
    num_kv_heads=2, head_dim=16, num_experts=16, moe_top_k=4,
    moe_intermediate=32, block="qwen3_moe", block_length=4,
    denoise_steps=4, mask_token_id=MASK,
)


def cfg_of(spec: LMSpec) -> dict:
    return dict(
        num_heads=spec.num_heads, num_kv_heads=spec.num_kv_heads,
        head_dim=spec.head_dim, top_k=spec.moe_top_k,
        rope_theta=spec.rope_theta, rms_eps=spec.rms_eps,
        block_length=spec.block_length, denoise_steps=spec.denoise_steps,
        mask_token_id=spec.mask_token_id, unmask=spec.unmask,
        unmask_threshold=spec.unmask_threshold,
    )


@pytest.fixture(scope="module")
def params():
    """Float32 weights, scaled up so that logits spread (std ~2) and
    some positions are confident: the strategies then differ."""
    p = sdar.init_params(SPEC, seed=1, dtype=jnp.float32)
    return jax.tree.map(lambda a: a * 12 if a.ndim > 1 else a, p)


def fp8_mm(x, w, *, transposed=False):
    """``sdar._mm`` computing in the control's precision: both operands
    rounded to float8 e4m3 with a per-tensor scale."""
    x, w = (ref._round_operand(a.astype(jnp.float32), "float8")
            for a in (x, w))
    if transposed:
        w = w.T
    return jnp.matmul(x, w, precision="highest")


# ---- the layer ------------------------------------------------------------


def test_dense_forward_matches_reference(params):
    toks = jax.random.randint(jax.random.key(0), (2, 24), 0, MASK)
    got = sdar.dense_logits(SPEC, params, toks)
    want = ref.forward(params, toks, cfg_of(SPEC))
    assert float(jnp.abs(want).max()) > 2.0
    assert float(jnp.abs(got - want).max()) < TOL


def test_dense_forward_in_float8_fails_the_tolerance(params, monkeypatch):
    monkeypatch.setattr(sdar, "_mm", fp8_mm)
    toks = jax.random.randint(jax.random.key(0), (2, 24), 0, MASK)
    got = sdar.dense_logits(SPEC, params, toks)
    want = ref.forward(params, toks, cfg_of(SPEC))
    assert float(jnp.abs(got - want).max()) > 50 * TOL


def test_rotary_and_qk_norm_match_reference(params):
    p = params["layers"]["1"]["self_attn"]
    u = jax.random.normal(jax.random.key(2), (1, 12, SPEC.d_model))
    pos = jnp.arange(12)[None]
    q, k, v = sdar.attn_qkv(SPEC, p, u, pos)
    cfg = cfg_of(SPEC)
    wq = ref.rotary(ref.rms_norm(
        ref.mm(u, p["q_proj"]).reshape(1, 12, 8, 16), p["q_norm"], 1e-6
    ), cfg["rope_theta"])
    wk = ref.rotary(ref.rms_norm(
        ref.mm(u, p["k_proj"]).reshape(1, 12, 2, 16), p["k_norm"], 1e-6
    ), cfg["rope_theta"])
    assert float(jnp.abs(q - wq).max()) < 1e-5
    assert float(jnp.abs(k - wk).max()) < 1e-5
    # position 0 is not rotated; a shift of both positions keeps q.k
    q0, _, _ = sdar.attn_qkv(SPEC, p, u[:, :1], jnp.zeros((1, 1), jnp.int32))
    heads = ref.rms_norm(ref.mm(u[:, :1], p["q_proj"]).reshape(1, 1, 8, 16),
                         p["q_norm"], 1e-6)
    assert float(jnp.abs(q0 - heads).max()) < 1e-6
    x = jax.random.normal(jax.random.key(3), (1, 2, 1, 16))
    a = sdar.rotary(x, jnp.asarray([[3, 7]]), 1e6)
    b = sdar.rotary(x, jnp.asarray([[103, 107]]), 1e6)
    dot = lambda r: float((r[0, 0, 0] * r[0, 1, 0]).sum())
    assert abs(dot(a) - dot(b)) < 1e-4


@pytest.mark.parametrize("impl,n", [("jnp", 40), ("pallas", 40),
                                    ("pallas", 3)])
def test_expert_layer_matches_reference_with_every_token_kept(
        params, impl, n):
    """``n`` 3: fewer assignments (12) than experts (16), the other
    branch of the padded layout's worst case."""
    p = params["layers"]["0"]["mlp"]
    u = jax.random.normal(jax.random.key(4), (n, SPEC.d_model))
    e = p["experts"]
    got, stats = jax.jit(lambda u: moe_ops.moe_layer(
        u, sdar._mm(u, p["gate"]), e["gate_proj"], e["up_proj"],
        e["down_proj"], top_k=4, impl=impl,
    ))(u)
    want = ref.moe(u, p, cfg_of(SPEC))
    assert float(jnp.abs(got - want).max()) < TOL
    assert int(stats[0]) == n * 4  # every assignment kept
    # one expert holding every row is the layout's worst case: no drop
    idx = jnp.zeros((n, 4), jnp.int32).at[:, 1:].set(
        jnp.arange(1, 4)[None])
    g = moe_ops.group_rows(idx, 16)
    assert int(g.counts[0]) == n and int(g.counts.sum()) == 4 * n
    rows = np.asarray(g.row_token)
    assert sorted(rows[rows < n].tolist()) == sorted(list(range(n)) * 4)


def test_expert_layer_in_float8_fails_the_tolerance(params):
    p = params["layers"]["0"]["mlp"]
    u = jax.random.normal(jax.random.key(4), (40, SPEC.d_model))
    want = ref.moe(u, p, cfg_of(SPEC))
    low = ref.moe(u, p, cfg_of(SPEC), "float8")
    assert float(jnp.abs(low - want).max()) > 50 * TOL


# ---- through the cache ------------------------------------------------------


def _prefill(spec, params, prompt, new_tokens, *, slot=1, slots=3):
    B = spec.block_length
    whole = len(prompt) // B * B
    cache = init_slot_cache(spec, slots)
    lanes = sdar.init_block_lanes(spec, slots)
    width = max(8, whole)
    chunk = np.zeros(width, np.int32)
    chunk[:whole] = prompt[:whole]
    tail = np.zeros(B, np.int32)
    tail[: len(prompt) - whole] = prompt[whole:]
    return jax.jit(
        lambda c, ln: sdar.prefill_chunk(
            spec, params, c, ln, jnp.int32(slot), jnp.asarray(chunk),
            jnp.int32(0), jnp.int32(whole), jnp.asarray(True),
            jnp.asarray(tail), jnp.int32(len(prompt) - whole),
            jnp.int32(new_tokens), jnp.int32(0), jnp.float32(0),
            jnp.float32(1), lane_attend=False,
        )
    )(cache, lanes)[:2]


@pytest.mark.parametrize("prompt_len", [8, 10, 3])
def test_block_logits_through_the_cache_match_the_full_forward(
        params, prompt_len, monkeypatch):
    """Prefill, then a block forward against the cache, against the
    reference's full forward of the same sequence; a prompt whose
    length is no multiple of the block opens the block unmasked."""
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(0, MASK, prompt_len).tolist()
    cache, lanes = _prefill(SPEC, params, prompt, 8)
    whole = prompt_len // 4 * 4
    assert int(cache.pos[1]) == whole and bool(lanes.active[1])
    block = prompt[whole:] + [MASK] * (4 - (prompt_len - whole))
    assert np.asarray(lanes.mask[1]).tolist() == [
        j >= prompt_len - whole for j in range(4)]
    toks = jnp.full((3, 4), MASK, jnp.int32).at[1].set(jnp.asarray(block))

    def fwd():
        return jax.jit(lambda c: sdar.block_forward(
            SPEC, params, c, toks, lanes.active))(cache)[0][1]

    want = ref.forward(
        params, jnp.asarray([prompt[:whole] + block]), cfg_of(SPEC)
    )[0, whole:]
    assert float(jnp.abs(fwd() - want).max()) < TOL
    monkeypatch.setattr(sdar, "_mm", fp8_mm)
    assert float(jnp.abs(fwd() - want).max()) > 50 * TOL


def test_flash_decode_folds_the_blocks_queries(params):
    """The same block logits through the Pallas decode kernel (the
    block's 4 queries as 32 rows a kv head) as through the jnp path."""
    prompt = np.random.default_rng(5).integers(0, MASK, 18).tolist()
    cache, lanes = _prefill(SPEC, params, prompt, 8)
    toks = jnp.full((3, 4), MASK, jnp.int32)
    out = {
        impl: jax.jit(lambda c: sdar.block_forward(
            SPEC, params, c, toks, lanes.active, attn_impl=impl))(cache)[0]
        for impl in ("reference", "flash")
    }
    assert float(jnp.abs(out["flash"] - out["reference"]).max()) < TOL


# ---- the engine -------------------------------------------------------------


def _serve(spec, params, jobs, *, stagger=True, **knobs):
    eng = ServeEngine(spec, params, slots=4, prefill_chunk=8, min_bucket=4,
                      max_queue=64, **knobs)
    eng.warmup()
    counts = dict(eng.compile_counts())
    rids = []
    for prompt, n in jobs:
        adm = eng.submit(prompt, n, record_blocks=True)
        assert adm.accepted, adm.reason
        rids.append(adm.request.rid)
        if stagger:
            eng.step()
    eng.run()
    assert eng.compile_counts() == counts  # nothing compiled after warmup
    return eng, [eng.result(r) for r in rids]


JOBS = [(10, 9), (3, 5), (16, 8), (21, 13), (7, 1), (12, 12), (9, 7)]


@pytest.mark.parametrize("unmask", sdar.UNMASK)
def test_engine_reproduces_the_reference_step_by_step(params, unmask):
    """Seven requests through four lanes, out of step, retired and
    admitted in mid-block: every request's forwards (which positions
    were masked, which tokens stood where, before each forward) and its
    tokens are the reference's, for both strategies; committed-token
    counts are exact."""
    spec = SPEC._replace(unmask=unmask, unmask_threshold=0.5)
    rng = np.random.default_rng(0)
    jobs = [(rng.integers(0, MASK, p).tolist(), n) for p, n in JOBS]
    eng, done = _serve(spec, params, jobs)
    forwards = many = 0
    for (prompt, n), c in zip(jobs, done):
        tokens, fw = ref.generate(params, prompt, n, cfg_of(spec))
        assert c.status == COMPLETE and c.tokens == tokens
        assert len(c.tokens) == n
        got = [(pos, toks, [bool(m) for m in mask])
               for pos, toks, mask in c.block_inputs]
        want = [(pos, toks.tolist(), mask.tolist())
                for pos, toks, mask, _ in fw]
        assert got == want
        forwards += len(fw)
        many += sum(len(taken) > 1 for *_, taken in fw)
        assert c.first_tokens == min(n, 4 - len(prompt) % 4)
        assert c.ttft is not None and c.tpot_s is None or c.tpot_s >= 0
    # the dynamic strategy took several positions at once somewhere
    assert (many > 0) == (unmask == "low_confidence_dynamic")
    s = eng.stats()
    assert s["tokens_total"] == sum(n for _, n in JOBS)
    bd = s["block_diffusion"]
    assert bd["tokens_committed_total"] == s["tokens_total"]
    assert bd["block_forwards_total"] == forwards
    assert bd["moe_tokens_routed_total"] > 0
    assert 1 <= bd["moe_expert_load_max"] <= 4 * 4 * 4
    assert s["compile_counts"]["block_step"] == 1


def test_static_schedule_costs_the_reckoned_forwards(params):
    """A prompt of 4k + 2 and an answer of 4m + 2... here 14 and 12:
    a first block of 3 forwards and 3 blocks of 5, the last one's
    surplus run but not counted."""
    prompt = np.random.default_rng(1).integers(0, MASK, 14).tolist()
    eng, (c,) = _serve(SPEC, params, [(prompt, 12)])
    assert len(c.block_inputs) == 3 + 3 * 5
    assert len(c.tokens) == 12
    assert eng.block_forwards_total == 18
    assert eng.blocks_committed_total == 4
    assert eng.tokens_committed_total == 12  # 2 + 4 + 4 + 2 of the last 4


def _admissions(tracer):
    """Requests bound to a lane, engine step by engine step."""
    return [nums[0] for name, _, _, _, nums in tracer.ring()
            if name == "serve.admit"]


@pytest.mark.parametrize("every", [0, 5])
def test_spaced_admission_spreads_lanes_of_equal_requests(params, every):
    """Eight requests of one length on four lanes, all queued before the
    first step. Unspaced, the four lanes are filled in one step; with ``admit_every`` 5 one
    request is bound every fifth step while a lane runs, an idle
    engine's first at once, and the answers are the same tokens."""
    from ddp_tpu.obs.tracer import Tracer

    rng = np.random.default_rng(3)
    jobs = [(rng.integers(0, MASK, 14).tolist(), 12) for _ in range(8)]
    tracer = Tracer()
    eng, done = _serve(SPEC, params, jobs, stagger=False, tracer=tracer,
                       admit_every=every)
    for (prompt, n), c in zip(jobs, done):
        assert c.status == COMPLETE
        assert c.tokens == ref.generate(params, prompt, n, cfg_of(SPEC))[0]
    assert eng.stats()["prefill"]["admit_every"] == every
    adm = _admissions(tracer)
    assert sum(adm) == 8 and adm[0] >= 1
    at = [i for i, n in enumerate(adm) for _ in range(n)]
    gaps = np.diff(at)
    if every:
        assert max(adm) == 1 and gaps.min() >= every
        # 20 steps a request on 4 lanes: the spacing starves no lane
        # for long, so the whole takes few steps more than unspaced
        assert len(adm) <= 2 * 20 + 4 * every + 4
    else:
        assert adm[0] == 4  # every free lane at once


def test_admit_every_is_validated(params):
    with pytest.raises(ValueError, match="admit_every"):
        ServeEngine(SPEC, params, slots=2, prefill_chunk=8, min_bucket=4,
                    admit_every=-1)


def test_seeded_sampling_is_reproducible(params):
    prompt = np.random.default_rng(2).integers(0, MASK, 9).tolist()
    outs = []
    for seed in (7, 7, 8):
        eng = ServeEngine(SPEC, params, slots=2, prefill_chunk=8,
                          min_bucket=4)
        adm = eng.submit(prompt, 10, temperature=1.0, seed=seed)
        eng.run()
        outs.append(eng.result(adm.request.rid).tokens)
    assert outs[0] == outs[1] and outs[0] != outs[2]
    assert all(len(o) == 10 for o in outs)


def test_admission_reserves_the_blocks_overhang(params):
    eng = ServeEngine(SPEC, params, slots=2, prefill_chunk=8, min_bucket=4)
    ok = eng.submit([1] * 10, 64 - 3 - 10)
    over = eng.submit([1] * 10, 64 - 3 - 10 + 1)
    assert ok.accepted and not over.accepted
    eng.run()
    assert len(eng.result(ok.request.rid).tokens) == 51


def test_timeout_evicts_in_mid_block_with_what_was_committed(params):
    now = [0.0]
    eng = ServeEngine(SPEC, params, slots=2, prefill_chunk=8, min_bucket=4,
                      clock=lambda: now[0])
    adm = eng.submit([5] * 9, 30, timeout=10.0)
    other = eng.submit([6] * 6, 6)
    for _ in range(12):
        eng.step()
    now[0] = 11.0
    eng.run()
    c = eng.result(adm.request.rid)
    assert c.status == TIMEOUT_EVICTED and 0 < len(c.tokens) < 30
    assert len(eng.result(other.request.rid).tokens) == 6
    # the lane is reused, and its new request is the reference's again
    prompt = np.random.default_rng(3).integers(0, MASK, 11).tolist()
    again = eng.submit(prompt, 7)
    eng.run()
    assert eng.result(again.request.rid).tokens == ref.generate(
        params, prompt, 7, cfg_of(SPEC))[0]


@pytest.mark.parametrize("knobs,match", [
    (dict(page_size=16), "fixed fp32 lanes"),
    (dict(kv_dtype="int8"), "fixed fp32 lanes"),
    (dict(min_bucket=2), "narrower than the model's block"),
])
def test_knobs_that_do_not_apply_are_refused(params, knobs, match):
    with pytest.raises(ValueError, match=match):
        ServeEngine(SPEC, params, slots=2, prefill_chunk=8,
                    **{"min_bucket": 4, **knobs})


@pytest.mark.parametrize("change,match", [
    (dict(block_length=0), "generates by blocks"),
    (dict(denoise_steps=3), "must divide"),
    (dict(mask_token_id=97), "outside the vocabulary"),
    (dict(unmask="random"), "unmask must be"),
])
def test_spec_that_names_no_such_model_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        sdar.validate(SPEC._replace(**change))


def test_stats_and_metricsz_count_committed_tokens(params):
    from ddp_tpu.obs.promtext import render_serve, validate_promtext
    from ddp_tpu.obs.tracer import get_tracer

    prompt = np.random.default_rng(4).integers(0, MASK, 6).tolist()
    eng, (c,) = _serve(SPEC, params, [(prompt, 5)])
    text = render_serve(eng.stats())
    validate_promtext(text)
    for line in ("ddp_tpu_serve_tokens_committed_total 5",
                 "ddp_tpu_serve_tokens_total 5",
                 "ddp_tpu_serve_blocks_committed_total 2",
                 f"ddp_tpu_serve_block_forwards_total {len(c.block_inputs)}"):
        assert line in text, line
    assert "ddp_tpu_serve_moe_tokens_routed_total" in text
    spans = [e for e in get_tracer().ring() if e[0] == "serve.block_step"]
    assert spans and all(len(e[4]) == 3 for e in spans[-3:])


# ---- from a checkpoint directory, beside a plain model ------------------------


def test_checkpoint_round_trip_recovers_the_spec(tmp_path, params):
    from ddp_tpu.train.checkpoint import (
        CheckpointManager,
        derive_spec_with_sidecar,
    )

    sdar.save_checkpoint(str(tmp_path), SPEC, params)
    mgr = CheckpointManager(str(tmp_path))
    restored, _, epoch = mgr.restore_for_inference(None)
    mgr.close()
    assert epoch == 0
    assert derive_spec_with_sidecar(
        str(tmp_path), restored, num_heads_fallback=4) == SPEC
    os.remove(os.path.join(str(tmp_path), "lm_spec.json"))
    with pytest.raises(ValueError, match="total_len"):
        derive_spec_with_sidecar(str(tmp_path), restored,
                                 num_heads_fallback=4)


def test_one_process_serves_a_plain_model_and_this_one(tmp_path, params):
    """``scripts/serve.py --init_demo --model sdar=DIR``: a GPT-2 block
    and a block-diffusion MoE behind one server, no flag for the
    latter: its spec is its checkpoint's."""
    import signal
    import urllib.request

    sdar.save_checkpoint(str(tmp_path / "sdar"), SPEC, params)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve.py"),
         "--init_demo", "--vocab_size", "64", "--seq_len", "32",
         "--slots", "2", "--port", "0",
         "--model", f"sdar={tmp_path / 'sdar'}"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        url = json.loads(proc.stdout.readline())["serving"]

        def post(body):
            req = urllib.request.Request(
                url + "/generate", data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=180) as resp:
                return json.loads(resp.read())

        plain = post({"prompt_tokens": [1, 2, 3], "max_new_tokens": 3})
        assert plain["status"] == "complete" and len(plain["tokens"]) == 3
        prompt = [5, 9, 2, 44, 17, 8]
        out = post({"prompt_tokens": prompt, "max_new_tokens": 7,
                    "model": "sdar", "record_blocks": True})
        assert out["status"] == "complete"
        assert out["tokens"] == ref.generate(
            params, prompt, 7, cfg_of(SPEC))[0]
        assert len(out["block_inputs"]) >= 3
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
