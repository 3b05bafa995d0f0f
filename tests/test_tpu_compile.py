"""Programs compiled for a TPU v5e that is described, not attached (the
TPU's compiler is installed here). Nothing runs.

- The block-diffusion model's kernels — the grouped expert matmuls and
  the decode kernel with a block's queries folded in — at the published
  widths of SDAR-30B-A3B. What the Pallas interpreter cannot show — a
  slice off the tiling, more fast memory than a kernel may use — fails
  here, at no chip time.
- The LM train step on mesh ``data=4`` (PR 29): the gradient all-reduces
  of the compiled, scheduled program are asynchronous and stand where
  compute runs under them; on one chip the step compiles to the program
  it always was (``scripts/show_collectives.py`` is the reader).
- The form of the blocks' ``ln2`` output (PR 45): held once on one chip,
  the parent's producer fusion on mesh ``data=4``.

The topology is described inside a fixture, in this file only: one
process at a time holds the TPU's library."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "scripts"))


@pytest.fixture(scope="module")
def topo():
    from show_collectives import describe_topology

    try:
        return describe_topology("v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps it from here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The kernels ask the backend whether to compile or interpret."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("tokens", [128, 64])  # a block step, a chunk
def test_grouped_expert_kernels_compile_at_published_widths(
        one_chip, as_on_tpu, tokens):
    from ddp_tpu.ops.moe import moe_layer

    d, f, E, k = 2048, 768, 128, 8
    bf = jnp.bfloat16
    compiled = jax.jit(
        lambda x, r, g, u, w: moe_layer(x, r, g, u, w, top_k=k,
                                        impl="pallas")
    ).lower(
        _shape((tokens, d), jnp.float32, one_chip),
        _shape((tokens, E), jnp.float32, one_chip),
        _shape((E, d, f), bf, one_chip), _shape((E, d, f), bf, one_chip),
        _shape((E, f, d), bf, one_chip),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "moe_grouped_gate_up" in text and "moe_grouped_down" in text


def test_decode_kernel_compiles_with_a_blocks_queries_folded(
        one_chip, as_on_tpu):
    from ddp_tpu.ops.decode import decode_attention

    S, B, H, Hkv, Dh, L, depth = 32, 4, 32, 4, 128, 512, 7
    compiled = jax.jit(
        lambda q, k, v, pos: decode_attention(
            q, k, v, pos, impl="flash", layer=depth - 1)
    ).lower(
        _shape((S, H * B, Dh), jnp.float32, one_chip),
        _shape((depth, S, L, Hkv, Dh), jnp.float32, one_chip),
        _shape((depth, S, L, Hkv, Dh), jnp.float32, one_chip),
        _shape((S,), jnp.int32, one_chip),
    ).compile()
    assert "flash_decode" in compiled.as_text()


# ---- the LM train step's gradient all-reduces (parallel/ddp.py) ---------


@pytest.mark.parametrize("form", ["resident", "grid"])
@pytest.mark.parametrize("case", [
    "train_cells_bf16", "prefill_fp32", "more_queries_than_keys",
    "block_causal_4"])
def test_flash_training_kernels_compile_at_the_cells_shape(
        one_chip, request, case, form):
    """The flash kernels under Mosaic: the train cells' call (4 x 2048
    tokens, 16 heads of 128, bf16, blocks of 512), float32 inputs
    (float32 MXU operands), rows that see no key, and the block-causal
    mask. ``resident``, what the program chooses at these shapes: the
    forward on its grid and ONE backward kernel over a head held in
    VMEM, its walk unrolled; ``grid``: the backward's grid pair with
    its live-pair tables in scalar memory, as a head over the VMEM
    budget runs it (the budget handed to the planning function)."""
    from ddp_tpu.ops import flash

    if form == "grid":
        request.getfixturevalue("backward_over_budget")
    dtype, T, S, causal = {
        "train_cells_bf16": (jnp.bfloat16, 2048, 2048, True),
        "prefill_fp32": (jnp.float32, 2048, 2048, True),
        "more_queries_than_keys": (jnp.bfloat16, 2048, 1024, True),
        "block_causal_4": (jnp.bfloat16, 1024, 2048, 4),
    }[case]
    q = _shape((4, T, 16, 128), dtype, one_chip)
    kv = _shape((4, S, 16, 128), dtype, one_chip)
    text = jax.jit(jax.grad(
        lambda q, k, v: flash.flash_attention(
            q, k, v, causal, 512, 512, False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    )).lower(q, kv, kv).compile().as_text()
    kernels = {"resident": ("flash_fwd", "flash_dkv"),
               "grid": ("flash_fwd", "flash_dq", "flash_dkv")}[form]
    assert text.count("tpu_custom_call") >= len(kernels)
    for kernel in kernels:
        assert kernel in text
    assert ("flash_dq" in text) == (form == "grid")


def _projection_fwd_bwd(B, T, H, D, one_chip):
    """The compiled text of ``flash_attention_projection``'s forward and
    backward at [B, T, H·3·D] bf16 for the described chip."""
    from ddp_tpu.ops.flash import flash_attention_projection

    def fwd_bwd(qkv, g):
        out, vjp = jax.vjp(
            lambda x: flash_attention_projection(x, H, True, 512, 512, False),
            qkv)
        return out, vjp(g)

    return jax.jit(fwd_bwd).lower(
        _shape((B, T, H * 3 * D), jnp.bfloat16, one_chip),
        _shape((B, T, H * D), jnp.bfloat16, one_chip),
    ).compile().as_text()


def test_flash_kernels_compile_on_the_fused_projection(one_chip):
    """The train cells' own call: q, k, v are 128-lane column blocks of
    the ``qkv`` matmul's [4, 2048, 16·3·128] output, strided fetches
    Mosaic takes as they are, and the cotangent comes back as one array.
    The compiled forward-and-backward is TWO kernels since PR 39 (the
    resident backward writes a head's dq | dk | dv columns itself): XLA
    adds no copy or transpose of an operand (33.5 MB each) around them."""
    import re

    from ddp_tpu.ops.flash import _backward_form

    text = _projection_fwd_bwd(4, 2048, 16, 128, one_chip)
    for kernel in ("flash_fwd", "flash_dkv"):
        assert kernel in text
    assert "flash_dq" not in text
    moved = re.findall(
        r"= (\w+\[[\d,]+\])\S* (?:copy|transpose|concatenate|pad|slice)\(",
        text)
    assert moved == []
    form, vmem = _backward_form(2048, 2048, 128, jnp.bfloat16, 512, 512, True)
    print(f"resident backward at the cells' shape: vmem_limit_bytes {vmem} "
          f"({vmem / 2 ** 20:.1f} MiB)")
    assert form == "resident"


def test_resident_backward_compiles_at_the_largest_head_it_admits(one_chip):
    """The longest causal head in blocks of 512 whose backward the
    planning function still holds resident: 4096 (36 live pairs
    unrolled, 45 MiB of VMEM asked for); at 8192 the operands would
    still fit the budget but the 136 pairs do not unroll, and the grid
    pair runs."""
    from ddp_tpu.ops.flash import _backward_form

    cell = (128, jnp.bfloat16, 512, 512, True)
    longest = max(T for T in (2048, 4096, 8192, 16384)
                  if _backward_form(T, T, *cell)[0] == "resident")
    assert longest == 4096
    _, vmem = _backward_form(longest, longest, *cell)
    print(f"resident backward at T {longest}: vmem_limit_bytes {vmem} "
          f"({vmem / 2 ** 20:.1f} MiB)")
    text = _projection_fwd_bwd(1, longest, 2, 128, one_chip)
    assert "flash_dkv" in text and "flash_dq" not in text
    assert "flash_dq" in _projection_fwd_bwd(1, 2 * longest, 2, 128, one_chip)


# ---- the hybrid model's kernels at granite-4.0-h-micro's widths ----------


def test_state_update_kernel_compiles_in_place_at_published_widths(
        one_chip, as_on_tpu):
    """64 lanes of 64 heads x 64 channels x 128 state dimensions, 36
    layers stored: one ``ssm_state_update`` call whose output IS the
    stored buffer (4.8 GB never copied: the program's temporaries stay
    under a lane's worth)."""
    from ddp_tpu.ops import ssm

    S, H, P, N, layers = 64, 64, 64, 128, 36
    f32 = jnp.float32
    compiled = jax.jit(
        lambda s, x, dt, A, B, C, D, live: ssm.ssm_state_update(
            s, 17, x, dt, A, B, C, D, live, impl="pallas"),
        donate_argnums=(0,),
    ).lower(
        _shape((layers, S, N, H * P), f32, one_chip),
        _shape((S, H, P), f32, one_chip), _shape((S, H), f32, one_chip),
        _shape((H,), f32, one_chip), _shape((S, N), f32, one_chip),
        _shape((S, N), f32, one_chip), _shape((H,), f32, one_chip),
        _shape((S,), jnp.bool_, one_chip),
    ).compile()
    assert "ssm_state_update" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == layers * S * N * H * P * 4
    assert mem.temp_size_in_bytes < 64 * 2**20


def test_decode_kernel_compiles_with_heads_packed_on_lanes(
        one_chip, as_on_tpu):
    """4 queries a kv head of 64 at softmax scale 1/64 on rows stored
    ``[4, 64, 2048, 8 * 64]``, and one lane of them read for a prefill
    chunk: both take the stored buffer as it is (no copy of it among the
    temporaries)."""
    from ddp_tpu.ops.decode import packed_decode_attention, read_lane

    S, H, Hkv, Dh, L, depth = 64, 32, 8, 64, 2048, 4
    kv = _shape((depth, S, L, Hkv * Dh), jnp.float32, one_chip)
    compiled = jax.jit(
        lambda q, k, v, pos: packed_decode_attention(
            q, k, v, pos, layer=depth - 1, impl="flash", scale=1 / 64)
    ).lower(
        _shape((S, H, Dh), jnp.float32, one_chip), kv, kv,
        _shape((S,), jnp.int32, one_chip),
    ).compile()
    assert "flash_decode" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20
    lane = jax.jit(
        lambda k, slot: read_lane(k, 2, slot, impl="pallas").reshape(
            L, Hkv, Dh)
    ).lower(kv, _shape((), jnp.int32, one_chip)).compile()
    assert "read_lane" in lane.as_text()
    assert lane.memory_analysis().temp_size_in_bytes < 16 * 2**20


# ---- the SambaY decoder's kernels at Phi-4-mini-flash-reasoning's widths ----


def test_selective_kernels_compile_at_published_widths(one_chip, as_on_tpu):
    """64 lanes of ``[16, 5120]`` state, 9 layers stored: one
    ``selective_state_update`` call whose output IS the stored buffer,
    the decay formed in the kernel from ``dt`` and a tile of ``A``; and
    the prefill scan of a chunk of 512 with the state of a tile of
    channels held in VMEM."""
    from ddp_tpu.ops import ssm

    S, C, N, layers, T = 64, 5120, 16, 9, 512
    f32 = jnp.float32
    compiled = jax.jit(
        lambda s, x, dt, A, B, Cc, D, live: ssm.selective_state_update(
            s, 4, x, dt, A, B, Cc, D, live, impl="pallas"),
        donate_argnums=(0,),
    ).lower(
        _shape((layers, S, N, C), f32, one_chip),
        _shape((S, C), f32, one_chip), _shape((S, C), f32, one_chip),
        _shape((N, C), f32, one_chip), _shape((S, N), f32, one_chip),
        _shape((S, N), f32, one_chip), _shape((C,), f32, one_chip),
        _shape((S,), jnp.bool_, one_chip),
    ).compile()
    assert "selective_state_update" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == layers * S * N * C * 4
    assert mem.temp_size_in_bytes < 16 * 2**20
    scan = jax.jit(
        lambda x, dt, A, B, Cc, s: ssm.selective_scan(
            x, dt, A, B, Cc, s, impl="pallas")
    ).lower(
        _shape((T, C), f32, one_chip), _shape((T, C), f32, one_chip),
        _shape((N, C), f32, one_chip), _shape((T, N), f32, one_chip),
        _shape((T, N), f32, one_chip), _shape((N, C), f32, one_chip),
    ).compile()
    assert "selective_scan" in scan.as_text()
    assert scan.memory_analysis().temp_size_in_bytes < 16 * 2**20


@pytest.mark.parametrize("L,depth", [(512, 8), (4096, 1)])
def test_differential_decode_kernel_compiles_over_ring_and_shared_rows(
        one_chip, as_on_tpu, L, depth):
    """The two maps of 20 head pairs over 10 kv pairs of 128 lanes on a
    window's ring (8 layers of 512 rows) and on the shared rows (one
    layer of 4096): ``flash_decode`` takes the stored buffer as it is."""
    from ddp_tpu.ops.decode import diff_decode_attention

    S, H, Hkv, Dh = 64, 40, 20, 64
    kv = _shape((depth, S, L, Hkv * Dh), jnp.float32, one_chip)
    compiled = jax.jit(
        lambda q, k, v, pos: diff_decode_attention(
            q, k, v, pos, layer=depth - 1, impl="flash")
    ).lower(
        _shape((S, H, Dh), jnp.float32, one_chip), kv, kv,
        _shape((S,), jnp.int32, one_chip),
    ).compile()
    assert "flash_decode" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20


@pytest.mark.parametrize("block,config,arguments_gb,temporaries_gb", [
    # as PR 36 and PR 32 compiled them with the grid form: the walk's
    # four copy slots are VMEM, not HBM
    ("sambay", "phi-4-mini-flash-reasoning-serve.json", 13.30, 0.23),
    ("granite_hybrid", "granite-4.0-h-micro-serve.json", 13.48, 0.09),
])
def test_the_serve_cells_decode_programs_still_fit(
        one_chip, as_on_tpu, block, config, arguments_gb, temporaries_gb):
    """The whole decode program of the SambaY and the hybrid cell at
    the benchmark's sizes (64 lanes; bfloat16 weights, float32 rows and
    state), every ``flash_decode`` call the walk: arguments and
    temporaries as they were, inside one chip's 16 GB."""
    import importlib
    import json

    from benchmarks.drivers import granite_serve, sambay_serve
    from ddp_tpu.models.generate import init_slot_cache

    root = os.path.dirname(os.path.dirname(__file__))
    with open(os.path.join(root, "benchmarks", "configs", config)) as f:
        cfg = json.load(f)
    spec = {"sambay": sambay_serve,
            "granite_hybrid": granite_serve}[block].lm_spec(cfg)
    model = importlib.import_module(f"ddp_tpu.models.{block}")
    S = cfg["engine"]["slots"]
    described = lambda make: jax.tree.map(
        lambda a: _shape(a.shape, a.dtype, one_chip), jax.eval_shape(make))
    lanes = lambda dtype: _shape((S,), dtype, one_chip)
    compiled = jax.jit(
        lambda p, c, *a: model.slot_decode_sample_step(
            spec, p, c, *a, attn_impl="flash"),
        donate_argnums=(1,),
    ).lower(
        described(lambda: model.init_params(spec)),
        described(lambda: init_slot_cache(spec, S)),
        lanes(jnp.int32), lanes(jnp.uint32), lanes(jnp.int32),
        lanes(jnp.float32), lanes(jnp.float32),
    ).compile()
    assert "flash_decode" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert abs(mem.argument_size_in_bytes / 1e9 - arguments_gb) < 0.01
    assert abs(mem.temp_size_in_bytes / 1e9 - temporaries_gb) < 0.01


@pytest.mark.parametrize("tokens", [16, 2048])  # a decode step, a chunk
def test_an_expert_share_compiles_at_glm5_widths(one_chip, as_on_tpu,
                                                 tokens):
    """16 of 256 sigmoid-routed experts of [6144, 2048]: gate and up do
    not fit VMEM twice over, so the gate/up call walks the tiles a
    column block at a time (grid (2, tiles)); the down call keeps its
    grid."""
    from ddp_tpu.ops.moe import column_block, moe_share_layer

    d, f, E, held, k = 6144, 2048, 256, 16, 8
    assert column_block(d, f, 2) == 1024
    bf = jnp.bfloat16
    compiled = jax.jit(
        lambda x, r, b, g, u, w: moe_share_layer(
            x, r, g, u, w, top_k=k, first=0, scoring="sigmoid", bias=b,
            scale=2.5, impl="pallas")
    ).lower(
        _shape((tokens, d), jnp.float32, one_chip),
        _shape((tokens, E), jnp.float32, one_chip),
        _shape((E,), jnp.float32, one_chip),
        _shape((held, d, f), bf, one_chip),
        _shape((held, d, f), bf, one_chip),
        _shape((held, f, d), bf, one_chip),
    ).compile()
    text = compiled.as_text()
    assert "moe_grouped_gate_up" in text and "moe_grouped_down" in text


def _glm5_cell(one_chip):
    """The GLM-5 cell at the benchmark's sizes, described -> (spec, a
    chunk's width, what both its programs take first: parameters, the
    16 lanes' cache and the lanes' tokens, seeds, steps, temperatures and
    top-ps)."""
    import json

    from benchmarks.drivers import glm_dsa_serve
    from ddp_tpu.models import glm_dsa
    from ddp_tpu.models.generate import init_slot_cache

    root = os.path.dirname(os.path.dirname(__file__))
    with open(os.path.join(root, "benchmarks", "configs",
                           "glm-5-serve-ep16.json")) as f:
        cfg = json.load(f)
    spec = glm_dsa_serve.lm_spec(cfg)
    S = cfg["engine"]["slots"]
    described = lambda make: jax.tree.map(
        lambda a: _shape(a.shape, a.dtype, one_chip), jax.eval_shape(make))
    lanes = lambda dtype: _shape((S,), dtype, one_chip)
    return spec, cfg["engine"]["prefill_chunk"], (
        described(lambda: glm_dsa.init_params(spec)),
        described(lambda: init_slot_cache(spec, S, jnp.bfloat16)),
        lanes(jnp.int32), lanes(jnp.int32), lanes(jnp.int32),
        lanes(jnp.float32), lanes(jnp.float32))


def test_the_glm5_cells_decode_program_fits(one_chip, as_on_tpu):
    """The whole decode program of the GLM-5 cell at the benchmark's
    sizes (16 lanes of 17,408 positions; bfloat16 weights and rows):
    9.42 GB of weights and 2.99 GB of lanes as arguments, 0.09 GB of
    temporaries (the index scores, the gathered rows, the experts'
    tiles), inside one chip's 16 GB beside the chunk programs' 0.91 GB
    (compiled by hand, PERF.md section 4: ~50 s each). No whole-lane
    copy: with a latent row of 576 stored as it is the program held
    0.60 GB of temporaries and copied 321 MB fourteen times a step."""
    from ddp_tpu.models import glm_dsa

    spec, _, state = _glm5_cell(one_chip)
    compiled = jax.jit(
        lambda p, c, *a: glm_dsa.slot_decode_sample_step(spec, p, c, *a),
        donate_argnums=(1,),
    ).lower(*state).compile()
    text = compiled.as_text()
    assert "moe_grouped_gate_up" in text and "moe_grouped_down" in text
    mem = compiled.memory_analysis()
    assert abs(mem.argument_size_in_bytes / 1e9 - 12.42) < 0.02
    assert mem.temp_size_in_bytes / 1e9 < 0.3


def test_the_glm5_cells_chunk_program_attends_through_the_kernel(
        one_chip, as_on_tpu):
    """The cell's 2,048-wide ``prefill_chunk`` program (PR 46): its
    third pass is the ``latent_prefill`` kernel, found under the
    ``mla_prefill`` scope by the name the profiler gives its events
    (what ``_gd_common.scope_map`` joins on); no score array of all
    heads for a block of keys exists, and the temporaries lie under the
    0.91 GB the ``jnp`` walk's program held."""
    from benchmarks.layer_metrics._gd_common import scope_map
    from ddp_tpu.models import glm_dsa
    from ddp_tpu.obs.tracer import get_tracer

    spec, C, state = _glm5_cell(one_chip)
    form = ("kernel", glm_dsa.QUERY_TILE)
    assert glm_dsa.chunk_form(spec, C, spec.total_len, 640) == form
    one = lambda dtype: _shape((), dtype, one_chip)
    compiled = jax.jit(
        lambda p, c, *a: glm_dsa.prefill_chunk(spec, p, c, *a),
        donate_argnums=(1,),
    ).lower(
        *state, one(jnp.int32), _shape((C,), jnp.int32, one_chip),
        one(jnp.int32), one(jnp.int32), one(jnp.bool_), one(jnp.int32),
        one(jnp.float32), one(jnp.float32),
    ).compile()
    plan = [e[4] for e in get_tracer().ring() if e[0] == "dsa.plan"][-1]
    assert plan[0] == "prefill_chunk" and plan[-2:] == form
    text = compiled.as_text()
    scopes = scope_map(text)
    kernels = [inst for inst in scopes if inst.startswith("latent_prefill")]
    assert len(kernels) == spec.depth
    assert all(scopes[k] == "mla_prefill" for k in kernels)
    assert "f32[64,2048,512]" not in text
    mem = compiled.memory_analysis()
    assert abs(mem.argument_size_in_bytes / 1e9 - 12.42) < 0.02
    assert mem.temp_size_in_bytes / 1e9 < 0.91


@pytest.fixture(scope="module")
def ddp4_text(topo):
    """Width 1024, depth 4 (heads of 64) on mesh data=4, compiled the
    way the program compiles it on a TPU: ~20 s."""
    from show_collectives import compile_lm_step

    return compile_lm_step(
        topo.devices[:4], mesh_axes={"data": 4}, d_model=1024, depth=4,
    ).as_text()


@pytest.fixture(scope="module")
def ddp4_schedule(ddp4_text):
    from ddp_tpu.obs.xprof import collective_schedule

    return collective_schedule(ddp4_text)


@pytest.mark.parametrize("what", [
    "every_leaf_reduced_in_fp32",
    "asynchronous",
    "under_backward_compute",
    "compute_between_every_pair",
    "the_tied_embedding_runs_under_the_update",
])
def test_ddp4_step_reduces_gradients_under_compute(ddp4_schedule, what):
    s, reduces = ddp4_schedule["summary"], ddp4_schedule["reduces"]
    pairs = [r for r in reduces if r["done"] > r["start"]]
    if what == "every_leaf_reduced_in_fp32":
        # same bytes on the wire as the plain compile: every parameter,
        # four bytes each (the tuple with the loss's scalars adds none)
        n_params = (4 * (4 * 1024 * 1024 + 8 * 1024 * 1024 + 13 * 1024)
                    + 50257 * 1024 + 2048 * 1024 + 2 * 1024)
        assert s["bytes"] == 4 * n_params
    elif what == "asynchronous":
        assert s["asynchronous"] == len(pairs) >= 1
        assert s["asynchronous_bytes"] >= 0.9 * s["bytes"]
    elif what == "under_backward_compute":
        # What the compiler does with the options: it defers the
        # weight-gradient matmuls and fuses each with a reduce, so the
        # reduce of one block runs while another's backward computes.
        assert ddp4_schedule["last_backward"] is not None
        assert s["under_backward"] >= 4
        assert s["under_backward_bytes"] >= 0.25 * s["bytes"]
    elif what == "compute_between_every_pair":
        assert all(r["compute_between"] >= 1 for r in pairs)
    else:
        embed = max(reduces, key=lambda r: r["bytes"])
        assert embed["bytes"] == 4 * 50257 * 1024
        assert embed["done"] > embed["start"]
        assert embed["compute_between"] >= 1


def test_one_chip_step_compiles_to_the_same_program(topo):
    """``overlap_compile_options`` is ``{}`` on a one-chip mesh, so the
    program's own jit and a bare ``jax.jit`` give the same module."""
    from show_collectives import compile_lm_step

    kw = dict(mesh_axes={"data": 1}, d_model=512, depth=1, num_heads=4,
              vocab_size=1024, seq_len=1024, rows_per_chip=2)
    # A Mosaic kernel's serialized body names the innermost frames of
    # the stack it was traced under, which ``_program`` cannot strip.
    # The backward kernels' stack is short (the fused entry is called
    # straight from the attention module), so at the default ten frames
    # it would reach whoever called ``lower``: the one thing that
    # differs between the two compiles by construction.
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 3)
    try:
        ours = compile_lm_step(topo.devices[:1], overlap=True, **kw).as_text()
        bare = compile_lm_step(topo.devices[:1], overlap=False, **kw).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    assert "flash_dkv" in ours and "all-reduce" not in ours
    assert _program(ours) == _program(bare)


def test_one_chip_step_runs_head_and_loss_as_one_operation(topo):
    """The train cells' vocabulary (50257, which 128 does not divide) on
    one chip: since PR 42 no matmul of the compiled step takes a float32
    ``[rows, vocabulary]`` operand (the head's three take bfloat16, the
    vocabulary padded to 50304 and minor), and the exponentials over
    the logits are taken ONCE, by the pass that writes the logits'
    gradient for both gradient matmuls to read (the parent took them
    three times: for the loss, and inside dX's and dW's own fusions)."""
    import re

    from show_collectives import compile_lm_step

    text = compile_lm_step(
        topo.devices[:1], mesh_axes={"data": 1}, d_model=512, depth=2,
        num_heads=4, seq_len=1024, rows_per_chip=2).as_text()
    bodies = re.split(r"\n(?=%\S+ \(|ENTRY )", text)
    vocab = r"\[(?:\d+,)*(?:50257|50304)(?:,\d+)*\]"
    matmuls = [b for b in bodies if " convolution(" in b]
    assert len(matmuls) >= 3
    wide = [line.strip()[:160] for b in matmuls for line in b.split("\n")
            if " parameter(" in line and re.search(r"= f32" + vocab, line)]
    assert wide == []
    narrow = [line for b in matmuls for line in b.split("\n")
              if " parameter(" in line
              and re.search(r"= bf16\[2048,50304\]", line)]
    assert len(narrow) == 2  # dX and dW read the ONE bfloat16 array
    exps = [line for line in text.split("\n")
            if " exponential(" in line and re.search(r"= \w+" + vocab, line)]
    assert len(exps) == 1
    # and the schedule is still the one that keeps each layer's weight
    # gradient (fused with its Adam update) beside its backward, not the
    # depth-first one that puts them all off to the end of the step
    # (``ops/lm_head._head_loss_bwd``): block2's ``mlp1`` has both its
    # gradient matmuls behind it before block1's backward begins
    entry = text[text.index("\nENTRY"):].split("\n")
    at = lambda name: [i for i, line in enumerate(entry) if re.search(
        r'kind=kOutput.*op_name="[^"]*transpose\(jvp\(\)\)/CausalLM/'
        + name + '/dot_general"', line)]
    assert len(at("block2/mlp1")) == 2
    assert max(at("block2/mlp1")) < min(at("block1/mlp2"))


_SMALL = dict(d_model=512, depth=2, num_heads=4, vocab_size=1024,
              seq_len=1024, rows_per_chip=2)


@pytest.fixture(scope="module")
def one_chip_text(topo):
    """The step at width 512, depth 2, 2 x 1024 tokens, compiled for
    one chip (~10 s)."""
    from show_collectives import compile_lm_step

    return compile_lm_step(
        topo.devices[:1], mesh_axes={"data": 1}, **_SMALL).as_text()


def _matmul_operands(text, scope, layer):
    """``(result shapes, operand shapes)``, as ``dtype[dims]``, of each
    matmul fusion of the entry computation whose ``op_name`` is
    ``layer``'s ``dot_general`` under ``scope`` (``jvp()``: the forward;
    ``transpose(jvp())``: the backward's two). The operands are the
    parameters of the computation the fusion calls."""
    import re

    shape = r"\w+\[[\d,]*\]"
    bodies = {b.split(" ", 1)[0]: b
              for b in re.split(r"\n(?=%\S+ \(|ENTRY )", text)}
    out = []
    for line in text[text.index("\nENTRY"):].split("\n"):
        if "kind=kOutput" not in line or not re.search(
                r'op_name="[^"]*/' + re.escape(scope)
                + r"/(?:shard_map/)?CausalLM/block\d/"
                + layer + '/dot_general"', line):
            continue
        body = bodies[re.search(r"calls=(%[\w.\-]+)", line).group(1)]
        out.append((
            re.findall(shape, line.split(" fusion(", 1)[0]),
            [re.search(r"= (" + shape + ")", l).group(1)
             for l in body.split("\n") if " parameter(" in l]))
    return out


@pytest.mark.parametrize("scope", ["jvp()", "transpose(jvp())"])
def test_one_chip_step_holds_mlp1s_layer_norm_once(one_chip_text, scope):
    """On one chip (PR 45) ``mlp1``'s matmuls, forward and weight
    gradient (fused with its Adam update), read ``ln2``'s output as the
    ``bf16[rows, T, d_model]`` array it was written to once: no operand
    is a ``[d_model]`` scale or shift or a ``f32[rows, T]`` statistic,
    from which the parent's fusions re-derived it, the weight gradient's
    once an output tile."""
    found = _matmul_operands(one_chip_text, scope, "mlp1")
    if scope == "jvp()":
        found = [f for f in found if f[0] == ["bf16[2,1024,2048]"]]
    else:  # dW + Adam: parameter, nu, mu and the step's scalar out
        found = [f for f in found if f[0][0] == "f32[512,2048]"]
    assert len(found) == _SMALL["depth"]
    for _, operands in found:
        assert "bf16[2,1024,512]" in operands
        stray = [o for o in operands
                 if o.endswith("[512]") or o == "f32[2,1024]"]
        assert stray == [], (scope, operands)


def test_one_chip_step_keeps_qkvs_layer_norm_plain(one_chip_text):
    """``ln1 -> attn.qkv`` is NOT held, on any mesh: held, the forward
    ran 0.05 ms a call faster and the weight gradient 0.12 slower at the
    cells' widths (PERF.md section 6, PR 45), so ``qkv``'s forward
    still derives the LayerNorm inside its own fusion."""
    found = [f for f in _matmul_operands(one_chip_text, "jvp()", "attn/qkv")
             if f[0] == ["bf16[2,1024,1536]"]]
    assert len(found) == _SMALL["depth"]
    for _, operands in found:
        assert [o for o in operands if o.endswith("[512]")], operands


@pytest.mark.parametrize("layer, columns", [("mlp1", 4096), ("attn/qkv", 3072)])
def test_ddp4_step_keeps_the_plain_layer_norm(ddp4_text, layer, columns):
    """On mesh ``data=4`` the step is the parent's program: the forward
    matmul behind each LayerNorm still derives it inside its own fusion,
    from x, the ``[d_model]`` scale and shift and the row statistics
    (the weight-gradient matmuls' slack is what the gradient all-reduce
    rides there: ``parallel/ddp.norm_plan``)."""
    found = [f for f in _matmul_operands(ddp4_text, "jvp()", layer)
             if f[0] == [f"bf16[4,2048,{columns}]"]]
    assert len(found) == 4  # the fixture's depth
    for _, operands in found:
        assert [o for o in operands if o.endswith("[1024]")], operands


def _program(text: str) -> str:
    """A module's computations without what names the Python call stack
    they were traced under (the tables at its top, each ``metadata``)."""
    import re

    lines = text.split("\n")
    first = next(i for i, l in enumerate(lines)
                 if l.startswith(("%", "ENTRY")))
    return re.sub(r", metadata=\{[^{}]*\}", "",
                  "\n".join(lines[:1] + lines[first:]))
