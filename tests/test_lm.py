"""Causal LM over sequence parallelism: causality, parity, learning.

The reference has no language modeling anywhere; this pins the
framework's decoder path (models/lm.py): the causal mask must actually
prevent future leakage, the seq-sharded forward must match the dense
one bit-close across shard boundaries, and the dp×sp train step must
learn next-token prediction on deterministic progressions.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddp_tpu.data.sequences import synthetic_tokens
from ddp_tpu.models.lm import (
    LMSpec,
    LMTrainState,
    create_lm_train_state,
    dense_lm_apply,
    init_lm,
    make_lm_train_step,
)
from ddp_tpu.runtime.mesh import MeshSpec, make_mesh

SPEC = LMSpec(vocab_size=32, total_len=64, d_model=32, depth=2, num_heads=4)


def test_forward_shape_and_tied_embedding():
    params = init_lm(SPEC, seed=0)
    toks = jnp.asarray(synthetic_tokens(2, total_len=64, vocab_size=32))
    logits = dense_lm_apply(SPEC, params, toks)
    assert logits.shape == (2, 64, 32)
    # tied head: no separate output projection in the tree
    assert "embed" in params and "head" not in params


def test_causality_no_future_leakage():
    """Changing tokens after position t must not change logits ≤ t."""
    params = init_lm(SPEC, seed=1)
    toks = synthetic_tokens(1, total_len=64, vocab_size=32, seed=2)
    logits_a = np.asarray(dense_lm_apply(SPEC, params, jnp.asarray(toks)))
    perturbed = toks.copy()
    perturbed[:, 40:] = (perturbed[:, 40:] + 11) % 32
    logits_b = np.asarray(dense_lm_apply(SPEC, params, jnp.asarray(perturbed)))
    np.testing.assert_allclose(
        logits_a[:, :40], logits_b[:, :40], atol=1e-5
    )
    assert not np.allclose(logits_a[:, 40:], logits_b[:, 40:], atol=1e-3)


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
def test_sharded_forward_matches_dense(devices, strategy):
    spec = SPEC._replace(strategy=strategy)
    mesh = make_mesh(MeshSpec(data=2, seq=4), devices=devices)
    tx = optax.adam(1e-3)
    state = create_lm_train_state(spec, tx, mesh, seed=3)
    toks = jnp.asarray(synthetic_tokens(2, total_len=64, vocab_size=32, seed=4))

    # one non-donating step to get logits path exercised, then compare
    # the sharded forward against the dense reference directly
    from ddp_tpu.models.lm import _sharded_lm  # forward only

    import jax as _jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    model = _sharded_lm(spec)

    def per_shard(params, tok):
        off = lax.axis_index("seq") * tok.shape[1]
        return model.apply({"params": params}, tok, pos_offset=off)

    fwd = _jax.jit(
        _jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(), P("data", "seq")), out_specs=P("data", "seq"),
            check_vma=False,
        )
    )
    got = np.asarray(fwd(state.params, toks))
    want = np.asarray(dense_lm_apply(spec, state.params, toks))
    np.testing.assert_allclose(got, want, atol=3e-5)


def test_lm_learns_progressions(devices):
    """dp2×sp4: next-token accuracy far above chance within a few steps."""
    mesh = make_mesh(MeshSpec(data=2, seq=4), devices=devices)
    spec = SPEC
    tx = optax.adam(3e-3)
    state = create_lm_train_state(spec, tx, mesh, seed=0)
    step = make_lm_train_step(spec, tx, mesh)
    toks = synthetic_tokens(256, total_len=64, vocab_size=32, seed=5)
    first = last = None
    for i in range(100):
        batch = jnp.asarray(toks[(i * 8) % 256 : (i * 8) % 256 + 8])
        state, m = step(state, batch)
        if first is None:
            first = float(m.loss)
        last = m
    assert int(state.step) == 100
    # measured trajectory (seed 0): 3.47 → ~1.4 by step 100
    assert float(last.loss) < first * 0.6
    assert float(last.accuracy) > 0.25  # chance is 1/32 ≈ 0.03


def test_remat_variant_runs(devices):
    mesh = make_mesh(MeshSpec(data=1, seq=8), devices=devices)
    spec = SPEC._replace(remat=True)
    tx = optax.adam(1e-3)
    state = create_lm_train_state(spec, tx, mesh, seed=0)
    step = make_lm_train_step(spec, tx, mesh)
    toks = jnp.asarray(synthetic_tokens(4, total_len=64, vocab_size=32))
    state, m = step(state, toks)
    assert np.isfinite(float(m.loss))


def _records_since(name, since):
    """The ring's records of one name stamped at or after ``since`` (a
    ``time.perf_counter`` reading): chosen by the record's own start,
    not by its place in the ring, which turns over once earlier files
    in the same worker have filled it (the compile records do, since
    PR 38), after which "everything past the old length" is empty."""
    from ddp_tpu.obs.tracer import get_tracer

    return [e for e in get_tracer().ring()
            if e[0] == name and e[1] >= since]


# ---- the compile options of the data-parallel step (parallel/ddp.py) ----


@pytest.mark.parametrize(
    "backend, axes, zero, chosen",
    [
        ("cpu", dict(data=4), False, False),  # XLA:CPU refuses xla_tpu_*
        ("tpu", dict(data=4), False, True),  # the one case: pure DDP
        ("tpu", dict(data=8), False, True),
        ("tpu", dict(data=1), False, False),  # one chip: no collective
        ("tpu", dict(data=2, model=2), False, False),
        ("tpu", dict(data=2, seq=2), False, False),
        ("tpu", dict(data=2, fsdp=2), False, False),
        ("tpu", dict(data=2, expert=2), False, False),
        ("tpu", dict(data=4), True, False),  # ZeRO: other collectives
    ],
)
def test_overlap_options_follow_backend_and_mesh(
        devices, monkeypatch, backend, axes, zero, chosen):
    """The choice is made from what the code sees — the backend, the
    mesh, a ZeRO layout — and is ``{}`` everywhere but on a pure
    data-parallel TPU mesh, so every other program keeps its compile."""
    from ddp_tpu.parallel import ddp

    n = int(np.prod(list(axes.values())))
    mesh = make_mesh(MeshSpec(**axes), devices=devices[:n])
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    opts = ddp.overlap_compile_options(
        mesh, zero_layout=object() if zero else None
    )
    if chosen:
        assert opts == ddp._OVERLAP_OPTIONS and opts is not ddp._OVERLAP_OPTIONS
        assert opts["xla_enable_async_all_reduce"] == "true"
    else:
        assert opts == {}


@pytest.mark.parametrize("data", [1, 4])
def test_train_compile_record_once_per_compile(devices, data):
    """Each compile of the train step leaves ONE ``train.compile``
    span in the tracer's ring — lower + compile seconds, the gradient
    reduces of the compiled step, how many are asynchronous, how many
    start before the last backward kernel — and a call that hits the
    compiled step leaves none."""
    from ddp_tpu.obs.tracer import SPAN_NUMS

    records = functools.partial(_records_since, "train.compile")
    mesh = make_mesh(MeshSpec(data=data), devices=devices[:data])
    tx = optax.adam(1e-3)
    step = make_lm_train_step(SPEC, tx, mesh, donate=False)
    state = create_lm_train_state(SPEC, tx, mesh, seed=0)
    toks = jnp.asarray(synthetic_tokens(4, total_len=64, vocab_size=32))
    before = time.perf_counter()
    state1, m1 = step(state, toks)
    assert len(records(before)) == 1
    state2, _ = step(state1, toks)  # same signature: no compile
    assert len(records(before)) == 1 and step._cache_size() == 1
    step(state2, jnp.concatenate([toks, toks]))  # new shape: a compile
    step(state2, toks)  # the first shape again: its executable is kept
    recs = records(before)
    assert len(recs) == 2 and step._cache_size() == 2
    name, t0, dur, parent, nums = recs[0]
    assert dur > 0 and parent is None
    assert len(nums) == len(SPAN_NUMS["train.compile"])
    reduces, asynchronous, in_backward, under, nbytes, async_bytes = nums
    # XLA:CPU compiles no asynchronous all-reduce and no Pallas kernel
    assert (asynchronous, in_backward, under, async_bytes) == (0, 0, 0, 0)
    if data == 1:
        assert reduces == 0 and nbytes == 0
    else:
        n_param_bytes = 4 * sum(
            x.size for x in jax.tree.leaves(state.params))
        assert reduces >= 1 and nbytes >= n_param_bytes
    # the step the wrapper runs is the step jit would have run
    ref = jax.jit(make_lm_train_step(SPEC, tx, mesh, jit=False))
    _, m_ref = ref(state, toks)
    assert float(m1.loss) == float(m_ref.loss)


def test_flash_plan_record_once_per_traced_call(monkeypatch):
    """Tracing a flash training kernel leaves ONE ``flash.plan`` record
    in the tracer's ring — blocks, block pairs visited a (batch·head),
    of which masked, of which dead, operand dtype, where the operands
    lie, the kernel's form, its grid steps a (batch·head), the matmuls a
    pair costs in it and the VMEM bytes reckoned — and a call of the
    compiled program leaves none. At the train cells' shape every
    kernel visits the 10 live pairs of its 4 x 4 grid, 4 of them
    diagonal, none dead, and reads the fused projection where the
    ``qkv`` matmul wrote it; the backward is ONE kernel, ``flash_dkv``
    in its resident form (PR 39): one grid step a (batch·head), five
    matmuls a pair, no ``flash_dq``."""
    from ddp_tpu.obs.tracer import SPAN_NUMS
    from ddp_tpu.ops.flash import flash_attention

    records = functools.partial(_records_since, "flash.plan")
    def grad(block):
        return jax.jit(jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, True, block, block, True
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        ))

    from ddp_tpu.ops.flash import _backward_form

    q = jnp.ones((1, 64, 2, 16), jnp.float32)
    step = grad(16)
    before = time.perf_counter()
    step(q, q, q)
    recs = records(before)
    small = _backward_form(64, 64, 16, "float32", 16, 16, True)[1]
    forms = {"flash_fwd": ("grid", 10, 2, 0),
             "flash_dkv": ("resident", 1, 5, small)}
    assert sorted(r[4][0] for r in recs) == sorted(forms)
    for name, t0, dur, parent, nums in recs:
        assert dur == 0.0 and parent is None
        assert dict(zip(SPAN_NUMS["flash.plan"], nums)) == {
            "kernel": nums[0], "block_q": 16, "block_k": 16, "visited": 10,
            "diagonal": 4, "dead": 0, "operand_dtype": "float32",
            "operand_layout": "transposed", **dict(zip(
                ("form", "grid_steps", "matmuls_per_pair", "vmem_bytes"),
                forms[nums[0]]))}
    step(q, q, q)  # compiled: nothing is traced, nothing recorded
    assert len(records(before)) == 2
    # the cells' own call (4 x 2048 tokens, 16 heads of 128, bf16, blocks
    # of 512), traced and not run
    cell = jax.ShapeDtypeStruct((4, 2048, 16, 128), jnp.bfloat16)
    before = time.perf_counter()
    jax.eval_shape(grad(512), cell, cell, cell)
    vmem = _backward_form(2048, 2048, 128, "bfloat16", 512, 512, True)[1]
    forms = {"flash_dkv": ("resident", 1, 5, vmem),
             "flash_fwd": ("grid", 10, 2, 0)}
    assert sorted(r[4] for r in records(before)) == [
        (kernel, 512, 512, 10, 4, 0, "float32", "heads_last", *form)
        for kernel, form in forms.items()]
    # and as the cells make it: the train step of ``_sharded_lm`` on a
    # mesh whose ``seq`` axis has one member, one layer at the cells'
    # widths, for a backend that is a TPU (the kernel choice asks)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = LMSpec(vocab_size=64, total_len=2048, d_model=2048, depth=1,
                  num_heads=16)
    tx = optax.adam(1e-4)
    mesh = make_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    params = jax.eval_shape(lambda: init_lm(spec))
    state = LMTrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32), params=params,
        opt_state=jax.eval_shape(tx.init, params))
    step = make_lm_train_step(
        spec, tx, mesh, compute_dtype=jnp.bfloat16, jit=False)
    before = time.perf_counter()
    jax.eval_shape(step, state, jax.ShapeDtypeStruct((4, 2048), jnp.int32))
    assert sorted(r[4] for r in records(before)) == [
        (kernel, 512, 512, 10, 4, 0, "float32", "projection", *form)
        for kernel, form in forms.items()]


# ---- the form of the blocks' LayerNorm outputs (parallel/ddp.norm_plan) ----


@pytest.mark.parametrize(
    "axes, remat, form, reason",
    [
        (dict(data=1), False, "held", "one_device"),
        (dict(data=2), False, "plain", "data_parallel"),  # forced host devices
        (dict(data=4), False, "plain", "data_parallel"),
        (dict(data=1, seq=2), False, "plain", "sharded"),
        (dict(data=1, model=2), False, "plain", "sharded"),
        (dict(data=2, seq=2), False, "plain", "sharded"),
        (dict(data=2, fsdp=2), False, "plain", "sharded"),
        (dict(data=1), True, "plain", "remat"),
    ],
)
def test_norm_form_follows_the_mesh(devices, axes, remat, form, reason):
    """``held`` where the step's mesh has ONE device (no collective to
    cover), the parent's program on every other mesh, and a block that
    is rematerialised anyway stays plain: chosen from what the code
    sees, beside ``overlap_compile_options``. The step that
    ``make_lm_train_step`` builds on that mesh follows it: a held norm
    (a block's ``ln2``) is one ``optimization_barrier`` of the traced
    forward (the fused head has two of its own in either form)."""
    from ddp_tpu.parallel import ddp

    n = int(np.prod(list(axes.values())))
    mesh = make_mesh(MeshSpec(**axes), devices=devices[:n])
    assert ddp.norm_plan(mesh, remat=remat) == (form, reason)
    spec = SPEC._replace(remat=remat)
    tx = optax.adam(1e-3)
    state = jax.eval_shape(
        lambda: create_lm_train_state(spec, tx, mesh, seed=0))
    step = make_lm_train_step(spec, tx, mesh, jit=False)
    toks = jax.ShapeDtypeStruct((4, 64), jnp.int32)
    barriers = str(jax.make_jaxpr(step)(state, toks)).count(
        "optimization_barrier")
    assert barriers == 2 + (spec.depth if form == "held" else 0)


def _loss_and_grads(spec, mesh, compute_dtype):
    from ddp_tpu.models import lm

    forward, _ = lm._make_sharded_forward(spec, mesh, compute_dtype)
    metrics = lm._make_sharded_token_metrics(spec, mesh)

    def loss(params, toks):
        logits, _ = forward(params, toks, head=not metrics.fused)
        return metrics(logits, toks)[0]

    return jax.jit(jax.value_and_grad(loss))


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_held_and_plain_norms_train_alike(devices, monkeypatch, compute_dtype):
    """The two forms are one computation: ``ln2``'s float32 output is
    rounded to the compute dtype before ``mlp1`` in both, and held only
    says that it is made once. On one device (where the rule says
    ``held``; ``plain`` by steering the rule, in the test) the loss,
    every leaf's gradient and the parameters after two Adam steps are
    the same."""
    from ddp_tpu.models import lm

    dtype = jnp.dtype(compute_dtype).type
    mesh = make_mesh(MeshSpec(data=1), devices=devices[:1])
    toks = jnp.asarray(synthetic_tokens(4, total_len=64, vocab_size=32, seed=7))
    tx = optax.adam(1e-2)

    def build():
        step = make_lm_train_step(
            SPEC, tx, mesh, compute_dtype=dtype, donate=False)
        return _loss_and_grads(SPEC, mesh, dtype), step

    held = build()
    monkeypatch.setattr(
        lm, "norm_plan", lambda mesh, remat=False: ("plain", "data_parallel"))
    plain = build()
    state = create_lm_train_state(SPEC, tx, mesh, seed=3)
    (loss_h, grads_h), (loss_p, grads_p) = (
        f[0](state.params, toks) for f in (held, plain))
    assert float(loss_h) == float(loss_p)
    flat_p = dict(jax.tree_util.tree_leaves_with_path(grads_p))
    for path, g in jax.tree_util.tree_leaves_with_path(grads_h):
        np.testing.assert_array_equal(
            np.asarray(g, np.float32), np.asarray(flat_p[path], np.float32),
            err_msg=jax.tree_util.keystr(path))
    states = []
    for _, step in (held, plain):
        s = state
        for _ in range(2):
            s, m = step(s, toks)
        states.append((s, float(m.loss)))
    assert states[0][1] == states[1][1]
    for a, b in zip(jax.tree.leaves(states[0][0].params),
                    jax.tree.leaves(states[1][0].params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("data, want", [
    (1, ("held", "one_device", SPEC.depth,
         SPEC.depth * 4 * 64 * SPEC.d_model * 2)),
    (2, ("plain", "data_parallel", 0, 0)),
])
def test_norm_plan_record_once_per_traced_call(devices, data, want):
    """Tracing the train step's forward leaves ONE ``lm.norm_plan``
    record in the tracer's ring (the form, the reason, the LayerNorms
    held and their bytes in the compute dtype) and a call of the
    compiled step leaves none."""
    from ddp_tpu.obs.tracer import SPAN_NUMS

    records = functools.partial(_records_since, "lm.norm_plan")
    mesh = make_mesh(MeshSpec(data=data), devices=devices[:data])
    tx = optax.adam(1e-3)
    step = make_lm_train_step(
        SPEC, tx, mesh, compute_dtype=jnp.bfloat16, donate=False)
    state = create_lm_train_state(SPEC, tx, mesh, seed=0)
    toks = jnp.asarray(synthetic_tokens(4, total_len=64, vocab_size=32))
    before = time.perf_counter()
    state, _ = step(state, toks)
    recs = records(before)
    assert len(recs) == 1
    name, t0, dur, parent, nums = recs[0]
    assert dur == 0.0 and parent is None
    assert len(nums) == len(SPAN_NUMS["lm.norm_plan"]) and nums == want
    step(state, toks)  # compiled: nothing is traced, nothing recorded
    assert len(records(before)) == 1
