"""ddp_tpu.obs.xprof: compiled-program introspection.

Contracts pinned here:

1. **Instrumentation is transparent** — an instrumented step is
   bit-identical to the raw jit step, compiles exactly once per
   signature, and preserves ``_cache_size()`` (the serve engine's
   static-shape pin rides it).
2. **Disabled is free** — ``instrument`` is the identity (the very
   same function object), the sampler returns ``{}``, and an
   xprof-off trainer's metrics records keep the pre-xprof schema
   byte-for-byte (no new keys) — the tracer's disabled pin, applied
   to this layer.
3. **Cross-checks hold** — the analytic FLOPs estimators behind MFU
   agree with XLA's counted FLOPs within a per-family tolerance band
   for CNN/ResNet/ViT/LM (no estimator was found off-tolerance; the
   bands pin the measured ratios so future drift fails loudly), and
   the zero strategy's hand-priced ``comm_bytes`` agrees with the
   HLO-derived ring traffic at world 2.
4. **Recompiles carry culprits** — a shape change mid-run lands in
   the step attribution with the responsible label, shape-diff, and
   compile seconds.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ddp_tpu.obs.xprof import (
    DeviceMemorySampler,
    Xprof,
    parse_hlo_collectives,
    ring_collective_traffic,
    shape_diff,
    shape_signature,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- signatures ------------------------------------------------------


def test_shape_signature_and_diff():
    sig = shape_signature(
        (jnp.zeros((8, 28, 28, 1), jnp.uint8), jnp.zeros((8,), jnp.int32))
    )
    assert sig == "u8[8,28,28,1]|i32[8]"
    tree_sig = shape_signature(({"a": jnp.zeros((4,)), "b": jnp.zeros((2, 3))},))
    assert tree_sig == "tree(2 leaves, 10 elems)"
    d = shape_diff("u8[8,28,28,1]|i32[8]", "u8[4,28,28,1]|i32[8]")
    assert d == "arg0: u8[8,28,28,1]->u8[4,28,28,1]"
    assert "arity" in shape_diff("i32[8]", "i32[8]|i32[8]")
    assert shape_diff("i32[8]", "i32[8]") == "(identical signature)"


# ---- HLO collective parsing ------------------------------------------

_HLO_FIXTURE = """
HloModule jit_step
%fused (p: f32[64]) -> f32[64] { ... }
%ar = f32[1024]{0} all-reduce(f32[1024]{0} %g), replica_groups={}
%rs = f32[512]{0} reduce-scatter(f32[1024]{0} %g2), dimensions={0}
%ag = (f32[256]{0}, s32[]) all-gather(f32[128]{0} %p, s32[] %q)
%cps = bf16[32,8]{1,0} collective-permute-start(bf16[32,8]{1,0} %x)
%cpd = bf16[32,8]{1,0} collective-permute-done(bf16[32,8]{1,0} %cps)
%ags = (f32[128]{0}, f32[256]{0}) all-gather-start(f32[128]{0} %p2)
%agd = f32[256]{0} all-gather-done((f32[128]{0}, f32[256]{0}) %ags)
%scalar = f32[] all-reduce(f32[] %loss), to_apply=%add
%tar = f32[64,8]{1,0:T(8,128)} all-reduce(f32[64,8]{1,0:T(8,128)} %tg)
%sps = f32[512]{0:S(1)} reduce-scatter(f32[1024]{0:S(1)} %sg)
"""


def test_parse_hlo_collectives_synthetic():
    got = parse_hlo_collectives(_HLO_FIXTURE)
    # three all-reduces: f32[1024], the f32[] scalar, and the
    # TPU-layout-annotated f32[64,8]{1,0:T(8,128)} (tiling/memory-
    # space suffixes must parse — post-optimization TPU HLO carries
    # them on every shape)
    assert got["all-reduce"]["count"] == 3
    assert got["all-reduce"]["result_bytes"] == 4096 + 4 + 64 * 8 * 4
    assert got["reduce-scatter"]["count"] == 2
    assert got["reduce-scatter"]["result_bytes"] == 2048 + 2048
    # sync variadic tuple result: both elements counted; the ASYNC
    # pair contributes only its -done result (the -start tuple
    # aliases the operand buffer — counting it would overstate ~1.5x)
    assert got["all-gather"]["count"] == 2
    assert got["all-gather"]["result_bytes"] == (1024 + 4) + 1024
    # -done counted once, -start skipped
    assert got["collective-permute"]["count"] == 1
    assert got["collective-permute"]["result_bytes"] == 512
    # per-instance entries carry the payload split (groups absent here)
    assert [o["result_bytes"] for o in got["all-reduce"]["ops"]] == [
        4096, 4, 2048,
    ]
    assert all(o["groups"] is None for o in got["all-reduce"]["ops"])


def test_parse_hlo_variadic_tuple_with_index_comments():
    """XLA (jax 0.9) prints one variadic all-reduce over the whole
    gradient tree and annotates long tuples with ``/*index=5*/``
    position comments — in the result shapes and the operand list.
    The parser must count every element (it used to stop at the first
    comment and read 7 bytes where the program moved megabytes)."""
    hlo = (
        "%all-reduce = (f32[32]{0}, f32[3,3,1,32]{3,2,1,0}, f32[64]{0}, "
        "f32[3,3,32,64]{3,2,1,0}, f32[10]{0}, /*index=5*/f32[50176,10]{1,0}, "
        "f32[]) all-reduce(%a, %b, %c, %d, %e, /*index=5*/%f, %g), "
        "channel_id=1, replica_groups={{0,1}}, use_global_device_ids=true, "
        "to_apply=%region_7.8\n"
    )
    got = parse_hlo_collectives(hlo)["all-reduce"]
    elems = 32 + 3 * 3 * 32 + 64 + 3 * 3 * 32 * 64 + 10 + 50176 * 10 + 1
    assert got["count"] == 1 and got["result_bytes"] == 4 * elems
    assert got["ops"][0]["groups"] == [[0, 1]]


_HLO_SUBGROUP_FIXTURE = """
HloModule jit_hier
%rs = f32[256]{0} reduce-scatter(f32[1024]{0} %g), channel_id=1, replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
%ar = f32[256]{0} all-reduce(f32[256]{0} %rs), channel_id=2, replica_groups={{0,4},{1,5},{2,6},{3,7}}, to_apply=%add
%ag = f32[1024]{0} all-gather(f32[256]{0} %p), channel_id=3, replica_groups=[2,4]<=[8], dimensions={0}
%agt = f32[1024]{0} all-gather(f32[512]{0} %p2), channel_id=4, replica_groups=[4,2]<=[2,4]T(1,0), dimensions={0}
%ars = f32[64]{0} all-reduce-start(f32[64]{0} %x), channel_id=5, replica_groups={{0,1},{2,3},{4,5},{6,7}}
%ars.2 = f32[128]{0} all-reduce-start(f32[128]{0} %y), channel_id=6, replica_groups={{0,4},{1,5},{2,6},{3,7}}
%ard.2 = f32[128]{0} all-reduce-done(f32[128]{0} %ars.2)
%ard = f32[64]{0} all-reduce-done(f32[64]{0} %ars)
"""


def test_parse_hlo_subgroup_replica_groups():
    """Hierarchical collectives name SUB-groups: explicit nested-brace
    and iota (``[g,n]<=[N]``, optionally transposed) forms both parse
    to memberships, and the async pair inherits the ``-start`` line's
    groups (the ``-done`` line carries none)."""
    got = parse_hlo_collectives(_HLO_SUBGROUP_FIXTURE)
    rs = got["reduce-scatter"]["ops"]
    assert rs[0]["groups"] == [[0, 1, 2, 3], [4, 5, 6, 7]]
    ar = got["all-reduce"]["ops"]
    assert ar[0]["groups"] == [[0, 4], [1, 5], [2, 6], [3, 7]]
    # async pairs retire OUT of start order here (ard.2 before ard):
    # the done's operand NAME re-joins it to ITS start's groups — a
    # FIFO pairing would cross-wire the two
    assert ar[1]["groups"] == [[0, 4], [1, 5], [2, 6], [3, 7]]
    assert ar[1]["result_bytes"] == 512
    assert ar[2]["groups"] == [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert ar[2]["result_bytes"] == 256
    ag = got["all-gather"]["ops"]
    # iota [2,4]<=[8]: reshape(iota(8), [2,4]) — contiguous rows
    assert ag[0]["groups"] == [[0, 1, 2, 3], [4, 5, 6, 7]]
    # transposed iota [4,2]<=[2,4]T(1,0): strided slice-crossing pairs
    assert ag[1]["groups"] == [[0, 4], [1, 5], [2, 6], [3, 7]]


def test_ring_traffic_subgroup_aware():
    """An op ring-models over ITS OWN group size, not the world: the
    hierarchical step's cross-slice exchange of a 1/N shard over S
    slices prices 2·(S−1)/S of the SHARD — the whole point."""
    from ddp_tpu.obs.xprof import hlo_axis_traffic

    got = parse_hlo_collectives(_HLO_SUBGROUP_FIXTURE)
    t = ring_collective_traffic(got, world=8)
    # rs groups of 4: 3 · 1024-byte shard; ag groups of 4: (3/4)·4096
    # plus the transposed ag over groups of 2: (1/2)·4096; ar groups
    # of 2: 2·(1/2)·1024, async pairs 2·(1/2)·256 + 2·(1/2)·512
    assert t["reduce_scatter"] == 3 * 1024
    assert t["all_gather"] == int(0.75 * 4096) + int(0.5 * 4096)
    assert t["all_reduce"] == 1024 + 256 + 512
    # slice blocks of 4 (dcn outermost): the {0,4}-style groups cross
    split = hlo_axis_traffic(got, slice_size=4, world=8)
    assert split["dcn"]["all_reduce"] == 1024 + 512  # cross-slice psums
    assert split["dcn"]["all_gather"] == int(0.5 * 4096)  # transposed ag
    assert split["ici"]["reduce_scatter"] == 3 * 1024
    assert split["ici"]["all_reduce"] == 256  # within-slice async pair
    assert (
        split["ici"]["total"] + split["dcn"]["total"] == t["total"]
    )


def test_ring_collective_traffic_model():
    coll = {
        "all-reduce": {"count": 1, "result_bytes": 1000},
        "reduce-scatter": {"count": 1, "result_bytes": 500},
        "all-gather": {"count": 1, "result_bytes": 1000},
    }
    t = ring_collective_traffic(coll, world=2)
    assert t["all_reduce"] == 1000  # 2·(1/2)·1000
    assert t["reduce_scatter"] == 500  # (N-1)·shard = 1·500
    assert t["all_gather"] == 500  # (1/2)·1000
    assert t["total"] == 2000
    # world 1: no wire traffic whatever the program says
    assert ring_collective_traffic(coll, world=1)["total"] == 0


# ---- instrumentation -------------------------------------------------


def _cnn_step(mesh, donate=True):
    import optax

    from ddp_tpu.models import get_model
    from ddp_tpu.parallel.ddp import (
        create_train_state,
        make_train_step,
        replicate_state,
    )

    model = get_model("simple_cnn")
    tx = optax.sgd(0.01)
    state = replicate_state(
        create_train_state(model, tx, jnp.zeros((1, 28, 28, 1)), seed=0),
        mesh,
    )
    return make_train_step(model, tx, mesh, donate=donate), state


def _data(mesh, batch):
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(0)
    return (
        jax.device_put(
            rng.integers(0, 256, (batch, 28, 28, 1), dtype=np.uint8), sh
        ),
        jax.device_put(rng.integers(0, 10, (batch,)).astype(np.int32), sh),
    )


def test_instrument_aot_parity_and_ledger():
    """Instrumented dispatch is bit-identical to jit, compiles once,
    and the ledger entry carries compile time / FLOPs / memory."""
    from ddp_tpu.runtime.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    step, state = _cnn_step(mesh)
    xp = Xprof(enabled=True)
    wrapped = xp.instrument(step, "train_step")
    imgs, lbls = _data(mesh, 8)
    losses = []
    for _ in range(3):
        state, metrics = wrapped(state, imgs, lbls)
        losses.append(float(metrics.loss))
    assert wrapped._cache_size() == 1  # one signature, one compile
    assert xp.program_count == 1
    rec = xp.ledger_records()[0]
    assert rec["label"] == "train_step"
    assert "u8[8,28,28,1]" in rec["signature"]
    assert rec["compile_time_s"] > 0
    assert rec["flops"] > 0
    assert rec["memory"]["argument_bytes"] > 0
    assert rec["calls"] == 3
    assert "shape_diff" not in rec  # first compile of the label

    # bit-identity vs the raw jit step
    step2, state2 = _cnn_step(mesh)
    ref = []
    for _ in range(3):
        state2, m2 = step2(state2, imgs, lbls)
        ref.append(float(m2.loss))
    assert losses == ref


def test_instrument_recompile_is_attributed():
    from ddp_tpu.runtime.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    step, state = _cnn_step(mesh, donate=False)
    xp = Xprof(enabled=True)
    wrapped = xp.instrument(step, "train_step")
    state, _ = wrapped(state, *_data(mesh, 8))
    seq, events = xp.events_after(0)
    assert len(events) == 1
    state, _ = wrapped(state, *_data(mesh, 4))  # shape change
    assert wrapped._cache_size() == 2
    seq2, events2 = xp.events_after(seq)
    assert len(events2) == 1
    ev = events2[0]
    assert ev["label"] == "train_step"
    assert "u8[8,28,28,1]->u8[4,28,28,1]" in ev["shape_diff"]
    assert ev["compile_time_s"] > 0
    # the cursor is consumer-local: a fresh reader still sees both
    assert len(xp.events_after(0)[1]) == 2


def test_disabled_mode_is_identity():
    """The disabled pin: instrument returns the SAME object, the
    sampler returns {}, nothing accumulates."""
    xp = Xprof(enabled=False)

    def fn(x):
        return x

    assert xp.instrument(fn, "anything") is fn
    assert xp.program_count == 0
    assert xp.total_compile_s == 0.0
    assert xp.events_after(0) == (0, [])
    assert xp.ledger_records() == []
    sampler = DeviceMemorySampler(enabled=False)
    assert sampler.sample() == {}
    assert sampler.high_water_bytes == 0
    # no growing allocations across a hot disabled-mode loop (the
    # tracer pin, applied here)
    import tracemalloc

    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    for _ in range(20_000):
        xp.events_after(0)
        sampler.sample()
    growth = tracemalloc.get_traced_memory()[0] - base
    tracemalloc.stop()
    assert growth < 64 * 1024, f"disabled xprof leaked {growth} bytes"


def test_observe_only_fallback_for_non_jit():
    """A callable without .lower still ledgers (first-call wall time,
    flagged ``fallback``) — the bench epoch-runner path."""
    calls = []

    def runner(x):
        calls.append(x)
        return x * 2

    runner.steps_per_epoch = 7
    xp = Xprof(enabled=True)
    wrapped = xp.instrument(runner, "bench_epoch")
    assert wrapped.steps_per_epoch == 7  # attribute delegation
    assert wrapped(jnp.ones((3,))).shape == (3,)
    assert wrapped(jnp.ones((3,))).shape == (3,)
    assert len(calls) == 2
    rec = xp.ledger_records()[0]
    assert rec["fallback"] is True
    assert "flops" not in rec


# ---- the analytic-estimator cross-check ------------------------------
#
# XLA counts every op in the REAL train program (fwd + actual bwd +
# optimizer); the analytic estimators count matmul/conv terms × 3 by
# the community convention. The ratio measured/analytic is therefore
# family-shaped: near 1 for conv nets (contractions dominate), above 1
# for tiny transformers (norm/softmax/elementwise work the convention
# excludes). The bands below pin the ratios MEASURED on this image —
# an estimator regression (wrong depth walk, dropped term, bad scale)
# lands far outside them. No estimator was found off-tolerance.

_FAMILY_BANDS = {
    "simple_cnn": (0.80, 1.15),
    "resnet18": (0.70, 1.05),
    "vit_micro": (0.90, 1.40),
    "causal_lm": (1.00, 1.55),
}


def _measured_vs_analytic(name):
    import optax

    from ddp_tpu.obs import goodput
    from ddp_tpu.runtime.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    tx = optax.sgd(0.01)
    xp = Xprof(enabled=True)
    B = 4
    if name == "causal_lm":
        from ddp_tpu.models.lm import (
            LMSpec,
            create_lm_train_state,
            make_lm_train_step,
        )

        spec = LMSpec(
            vocab_size=64, total_len=64, d_model=32, depth=2, num_heads=4
        )
        state = create_lm_train_state(spec, tx, mesh, seed=0)
        step = xp.instrument(
            make_lm_train_step(spec, tx, mesh, donate=False), "train_step"
        )
        from jax.sharding import NamedSharding, PartitionSpec as P

        toks = jax.device_put(
            np.random.default_rng(0)
            .integers(0, 64, (B, 64))
            .astype(np.int32),
            NamedSharding(mesh, P("data")),
        )
        step(state, toks)
        analytic = goodput.lm_train_flops_per_sequence(spec) * B
    else:
        from ddp_tpu.models import get_model
        from ddp_tpu.parallel.ddp import (
            create_train_state,
            make_train_step,
            replicate_state,
        )
        from jax.sharding import NamedSharding, PartitionSpec as P

        shape = (32, 32, 3) if name == "resnet18" else (28, 28, 1)
        model = get_model(name)
        state = replicate_state(
            create_train_state(model, tx, jnp.zeros((1, *shape)), seed=0),
            mesh,
        )
        step = xp.instrument(
            make_train_step(model, tx, mesh, donate=False), "train_step"
        )
        sh = NamedSharding(mesh, P("data"))
        rng = np.random.default_rng(0)
        imgs = jax.device_put(
            rng.integers(0, 256, (B, *shape), dtype=np.uint8), sh
        )
        lbls = jax.device_put(
            rng.integers(0, 10, (B,)).astype(np.int32), sh
        )
        step(state, imgs, lbls)
        analytic = (
            goodput.train_flops_per_example(
                name, image_shape=shape, num_classes=10
            )
            * B
        )
    measured = next(
        (
            p["flops"]
            for p in reversed(xp.ledger_records())
            if p["label"] == "train_step" and "flops" in p
        ),
        None,
    )
    assert measured is not None and analytic
    return measured / analytic


@pytest.mark.parametrize("family", sorted(_FAMILY_BANDS))
def test_analytic_flops_within_family_tolerance(family):
    lo, hi = _FAMILY_BANDS[family]
    ratio = _measured_vs_analytic(family)
    assert lo <= ratio <= hi, (
        f"{family}: XLA-measured/analytic FLOPs ratio {ratio:.3f} "
        f"outside the pinned band [{lo}, {hi}] — the estimator (or "
        "XLA's counting) drifted"
    )


# ---- the comm-bytes cross-check (world 2, in-process) ----------------


def test_zero_comm_bytes_match_hlo_world2():
    """Acceptance pin: the zero strategy's hand-priced comm_bytes
    agrees with the compiled program's collectives at world 2 — and
    the ddp baseline's all-reduce pricing does too."""
    import optax

    from ddp_tpu.models import get_model
    from ddp_tpu.parallel.ddp import (
        create_train_state,
        make_train_step,
        replicate_state,
    )
    from ddp_tpu.parallel.zero import (
        create_zero_state,
        ddp_comm_bytes,
        make_zero_train_step,
        zero_comm_bytes,
    )
    from ddp_tpu.runtime.mesh import MeshSpec, make_mesh

    world = 2
    mesh = make_mesh(MeshSpec(data=world), devices=jax.devices()[:world])
    model = get_model("simple_cnn")
    tx = optax.adam(1e-3)
    sample = jnp.zeros((1, 28, 28, 1))
    xp = Xprof(enabled=True)

    zero_state, layout = create_zero_state(
        model, tx, sample, mesh, seed=0, bucket_mb=0.05
    )
    zero_step = xp.instrument(
        make_zero_train_step(model, tx, mesh, layout, donate=False), "zero"
    )
    ddp_state = replicate_state(
        create_train_state(model, tx, sample, seed=0), mesh
    )
    ddp_step = xp.instrument(
        make_train_step(model, tx, mesh, donate=False), "ddp"
    )
    imgs, lbls = _data(mesh, 8)
    zero_step(zero_state, imgs, lbls)
    ddp_step(ddp_state, imgs, lbls)

    zc = xp.comm_check(
        "zero", zero_comm_bytes(layout, world)["total"], world
    )
    assert zc["within_tolerance"], zc
    # the scatter+gather split is visible, the all_reduce term ~gone
    # (scalar metrics reductions only)
    assert zc["measured_by_kind"]["reduce_scatter"] > 0
    assert zc["measured_by_kind"]["all_gather"] > 0
    assert zc["measured_by_kind"].get("all_reduce", 0) < 1024

    dc = xp.comm_check(
        "ddp", ddp_comm_bytes(ddp_state.params, world)["total"], world
    )
    assert dc["within_tolerance"], dc
    assert dc["measured_by_kind"]["all_reduce"] > 0

    # a drifted estimate is CAUGHT, not averaged away
    bad = xp.comm_check("zero", 10 * zc["expected_comm_bytes"], world)
    assert not bad["within_tolerance"]


def test_comm_check_zero_expected_semantics():
    """Expected 0 passes iff the program really has no collectives."""
    xp = Xprof(enabled=True)
    f = xp.instrument(jax.jit(lambda x: x * 2), "pure")
    f(jnp.ones((4,)))
    check = xp.comm_check("pure", 0, world=2)
    assert check["within_tolerance"] and check["measured_comm_bytes"] == 0
    # unknown label → None (nothing compiled under it)
    assert xp.comm_check("nope", 0, world=2) is None


# ---- device-memory sampler -------------------------------------------


def test_memory_sampler_live_buffer_accounting():
    sampler = DeviceMemorySampler(enabled=True, devices=jax.devices()[:1])
    base = sampler.sample()
    assert base["hbm_source"] in ("memory_stats", "live_buffers")
    big = jax.device_put(
        np.zeros((256, 1024), np.float32), jax.devices()[0]
    )
    jax.block_until_ready(big)
    grown = sampler.sample()
    assert grown["hbm_used_bytes"] >= base["hbm_used_bytes"] + big.nbytes // 2
    high = grown["hbm_high_water_bytes"]
    assert high >= grown["hbm_used_bytes"] or high >= base["hbm_used_bytes"]
    del big
    shrunk = sampler.sample()
    # high-water is monotone even after the buffer is freed
    assert shrunk["hbm_high_water_bytes"] >= high
    assert sampler.high_water_bytes == shrunk["hbm_high_water_bytes"]


# ---- steptime: recompiles carry culprits -----------------------------


def test_steptime_recompile_culprit():
    from ddp_tpu.obs.steptime import StepAttributor

    xp = Xprof(enabled=True)
    f = xp.instrument(jax.jit(lambda x: (x * 2).sum()), "hot_fn")
    attr = StepAttributor(enabled=True, xprof=xp)
    batches = [jnp.ones((4,)), jnp.ones((4,)), jnp.ones((8,))]
    timings = []
    for b in attr.batches(batches):
        out = f(b)
        timings.append(attr.on_step(out))
    # batch 0: first compile, attributed
    assert timings[0].recompiles >= 1
    assert timings[0].compiles[0]["label"] == "hot_fn"
    assert timings[0].compiles[0]["compile_time_s"] > 0
    # batch 1: cache hit — no recompile, no culprits
    assert timings[1].recompiles == 0 and timings[1].compiles is None
    # batch 2: shape change — culprit carries the diff
    assert timings[2].recompiles >= 1
    assert "f32[4]->f32[8]" in timings[2].compiles[0]["shape_diff"]


# ---- tracer counter track + trace_merge ------------------------------


def test_tracer_counter_track_merges(tmp_path):
    import subprocess
    import sys

    from ddp_tpu.obs.tracer import Tracer, validate_trace_file

    t = Tracer(enabled=True, process_id=0)
    t.counter("hbm", {"used_bytes": 100, "high_water_bytes": 100})
    t.counter("hbm", {"used_bytes": 60, "high_water_bytes": 120})
    path = t.export(str(tmp_path / "trace_rank0.trace.json"))
    doc = validate_trace_file(path)
    cs = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert len(cs) == 2 and cs[0]["args"]["used_bytes"] == 100
    # disabled: free, records nothing
    t_off = Tracer(enabled=False)
    t_off.counter("hbm", {"used_bytes": 1})
    assert t_off.trace_document()["traceEvents"][1:] == []

    merged = tmp_path / "merged.trace.json"
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "scripts", "trace_merge.py"),
            str(tmp_path),
            "-o",
            str(merged),
        ],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr
    side = json.load(open(merged))["ddp_tpu"]
    assert side["counters"]["hbm:used_bytes"] == {"samples": 2, "max": 100}
    assert side["counters"]["hbm:high_water_bytes"]["max"] == 120


# ---- promtext gauges -------------------------------------------------


def test_promtext_xprof_gauges_lint_clean():
    from ddp_tpu.obs.promtext import (
        render_serve,
        render_train,
        validate_promtext,
    )

    snap = {
        "step": 10, "loss": 1.0,
        "compile_programs": 2, "compile_seconds_total": 1.25,
        "hbm_used_bytes": 1000, "hbm_high_water_bytes": 2000,
        "hbm_headroom_frac": 0.75,
    }
    text = render_train(snap)
    validate_promtext(text)
    for name in (
        "ddp_tpu_train_compiled_executables",
        "ddp_tpu_train_compile_seconds_total",
        "ddp_tpu_train_hbm_high_water_bytes",
        "ddp_tpu_train_hbm_headroom_frac",
    ):
        assert name in text
    # absent keys render nothing: the xprof-off exposition is unchanged
    off = render_train({"step": 10, "loss": 1.0})
    assert "hbm" not in off and "compile" not in off

    stats = {
        "slots": 2, "active": 0, "queue_depth": 0, "steps": 1,
        "xprof": {
            "programs": 5, "compile_s_total": 3.2,
            "hbm": {"hbm_used_bytes": 10, "hbm_high_water_bytes": 20},
        },
    }
    stext = render_serve(stats, up=True)
    validate_promtext(stext)
    assert "ddp_tpu_serve_compile_seconds_total" in stext
    assert "ddp_tpu_serve_hbm_high_water_bytes" in stext
    off_s = render_serve(
        {"slots": 2, "active": 0, "queue_depth": 0, "steps": 1}, up=True
    )
    assert "hbm" not in off_s and "compile_seconds" not in off_s


# ---- flight recorder provider ----------------------------------------


def test_recorder_provider_lands_in_dump(tmp_path):
    from ddp_tpu.obs.recorder import FlightRecorder, load_dump

    rec = FlightRecorder(str(tmp_path), rank=0, capacity=8)
    rec.set_provider(
        "xprof",
        lambda: {"compile_ledger": [{"label": "train_step"}],
                 "memory": {"hbm_used_bytes": 123}},
    )
    rec.set_provider("broken", lambda: 1 / 0)
    rec.record("step", step=1)
    path = rec.dump("test")
    doc = load_dump(path)
    assert doc["extras"]["xprof"]["memory"]["hbm_used_bytes"] == 123
    assert doc["extras"]["xprof"]["compile_ledger"][0]["label"] == "train_step"
    # a raising provider marks itself and never kills the dump
    assert doc["extras"]["broken"] == {"provider_error": "ZeroDivisionError"}
    assert doc["records"][0]["kind"] == "step"


# ---- serve engine ----------------------------------------------------


def test_serve_engine_xprof_ledger_and_parity():
    from ddp_tpu.models.lm import LMSpec, init_lm
    from ddp_tpu.serve.engine import ServeEngine

    spec = LMSpec(
        vocab_size=64, total_len=32, d_model=32, depth=1, num_heads=2
    )
    params = init_lm(spec, seed=0)
    xp = Xprof(enabled=True)
    eng = ServeEngine(spec, params, slots=2, xprof=xp)
    counts = eng.warmup()
    # the whole program set is ledgered with engine labels
    labels = {r["label"] for r in xp.ledger_records()}
    assert labels == {
        "serve.prefill_first", "serve.prefill_chunk", "serve.decode",
    }
    assert xp.program_count == sum(counts.values())
    assert xp.total_compile_s > 0
    eng.submit([1, 2, 3], 4)
    out = eng.run()

    eng2 = ServeEngine(spec, params, slots=2)  # uninstrumented
    eng2.warmup()
    eng2.submit([1, 2, 3], 4)
    out2 = eng2.run()
    assert out[0].tokens == out2[0].tokens  # token identity holds
    # static-shape pin survives instrumentation: traffic compiled 0 new
    assert eng.compile_counts() == counts
    s = eng.stats()
    assert s["xprof"]["programs"] == sum(counts.values())
    assert s["xprof"]["hbm"]["hbm_used_bytes"] > 0
    assert "xprof" not in eng2.stats()  # off = byte-identical stats


# ---- trainer end-to-end ----------------------------------------------


def _train_config(tmp_path, **kw):
    from ddp_tpu.train.config import TrainConfig

    defaults = dict(
        epochs=1,
        batch_size=4,
        checkpoint_dir=str(tmp_path / "ck"),
        data_root=str(tmp_path / "data"),
        synthetic_data=True,
        synthetic_size=256,
        log_interval=2,
        eval_every=0,
        metrics_file=str(tmp_path / "metrics.jsonl"),
    )
    defaults.update(kw)
    return TrainConfig(**defaults)


def _records(tmp_path):
    lines = (tmp_path / "metrics.jsonl").read_text().splitlines()
    return [json.loads(l) for l in lines]


def test_trainer_xprof_end_to_end(tmp_path):
    """--xprof acceptance: compile records carry the train_step label,
    step/epoch records carry the HBM high-water, the comm cross-check
    lands (world 8 in-process), and the flight recorder dumps the
    ledger."""
    from ddp_tpu.obs.recorder import load_dump
    from ddp_tpu.train.trainer import Trainer

    t = Trainer(_train_config(tmp_path, xprof=True))
    assert t._xprof.enabled
    t.train()

    recs = _records(tmp_path)
    compiles = [r for r in recs if r["kind"] == "compile"]
    assert any(c["label"] == "train_step" for c in compiles)
    assert all(c["compile_time_s"] > 0 for c in compiles)
    steps = [r for r in recs if r["kind"] == "step"]
    assert steps and all("hbm_used_bytes" in r for r in steps)
    assert all("hbm_high_water_bytes" in r for r in steps)
    epoch = next(r for r in recs if r["kind"] == "epoch")
    assert epoch["hbm_high_water_bytes"] > 0
    assert epoch["compile_s"] > 0
    assert epoch["compiled_programs"] >= 1
    # the ddp baseline's comm estimate was cross-checked against HLO
    # (the suite runs 8 emulated devices, so world is 8 here)
    check = next(r for r in recs if r["kind"] == "xprof_check")
    assert check["within_tolerance"], check
    assert check["label"] == "train_step"
    # OOM forensics: the dump carries the ledger + a memory sample
    dump = t._recorder.dump("test")
    doc = load_dump(dump)
    ledger = doc["extras"]["xprof"]["compile_ledger"]
    assert any(e["label"] == "train_step" for e in ledger)
    assert doc["extras"]["xprof"]["memory"]["hbm_used_bytes"] > 0
    t.close()


def test_trainer_xprof_disabled_schema_unchanged(tmp_path):
    """The disabled pin: no instrumentation wrapper on the hot path,
    no xprof record kinds, no new step/epoch keys — the metrics
    stream only widens under --xprof."""
    from ddp_tpu.obs.xprof import _Instrumented
    from ddp_tpu.train.trainer import Trainer

    t = Trainer(_train_config(tmp_path))
    assert t._xprof.enabled is False
    assert not isinstance(t.train_step, _Instrumented)
    assert not isinstance(t.eval_step, _Instrumented)
    t.train()
    t.close()
    recs = _records(tmp_path)
    assert not [r for r in recs if r["kind"] in ("compile", "xprof_check")]
    for r in recs:
        assert "hbm_used_bytes" not in r
        assert "hbm_high_water_bytes" not in r
        assert "compile_s" not in r


def test_trainer_xprof_rejects_fast_epoch(tmp_path):
    from ddp_tpu.train.trainer import Trainer

    with pytest.raises(ValueError, match="xprof"):
        Trainer(_train_config(tmp_path, xprof=True, fast_epoch=True))
