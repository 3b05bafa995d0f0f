"""ops/flash.pick_block: the one block rule of the flash and
flash-decode kernels, and where its result surfaces.

Largest tile-aligned divisor property, the engine's refusal of a lane
no block tiles, kernel-vs-reference parity on a non-divisible L, and
the xprof ``annotate`` plumbing that puts the effective block in the
compile ledger.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_tpu.models.lm import LMSpec
from ddp_tpu.ops.decode import (
    decode_attention_reference,
    flash_decode_attention,
)
from ddp_tpu.ops.flash import pick_block
from ddp_tpu.serve.engine import resolve_engine_knobs

SPEC = LMSpec(vocab_size=37, total_len=32, d_model=32, depth=1, num_heads=2)


class TestPickBlock:
    """ops/flash.pick_block: the one block rule of the flash and
    flash-decode kernels (tile-aligned divisor, or raise)."""

    def test_regression_non_divisible_requested(self):
        """The ISSUE-18 pin: L=48 with the default 32 request must land
        on 24 (largest aligned divisor ≤ 32), not degrade to a
        full-length block that defeats the dead-block skip."""
        assert pick_block(48, 32, jnp.float32) == 24

    @pytest.mark.parametrize(
        "L,req,dtype,expect",
        [
            (128, 128, jnp.float32, 128),
            (7, 128, jnp.float32, 7),  # fits the request: whole lane
            (48, 16, jnp.float32, 16),
            (2048, 512, jnp.bfloat16, 512),
            (2064, 128, jnp.float32, 48),  # 2064 = 16·3·43
            (96, 64, jnp.int8, 32),  # int8 rows tile 32 at a time
        ],
    )
    def test_known_values(self, L, req, dtype, expect):
        assert pick_block(L, req, dtype) == expect

    @pytest.mark.parametrize(
        "L,req,dtype",
        [
            (97, 64, jnp.float32),  # prime: used to degrade to 1-wide
            (200, 128, jnp.bfloat16),  # 8-aligned divisors only
            (2064, 128, jnp.int8),  # the paged int8 case: 16 | L, 32 ∤ L
        ],
    )
    def test_non_dividing_length_raises_with_shape(self, L, req, dtype):
        with pytest.raises(ValueError, match=f"length-{L}"):
            pick_block(L, req, dtype)

    def test_flash_blocks_never_fall_to_whole_sequence(self):
        """ops/flash._pick_blocks used to make the block the WHOLE
        sequence when the request did not divide it (one [T, T] cell
        in VMEM at long T); now it is an aligned divisor or an
        error."""
        from ddp_tpu.ops.flash import _pick_blocks

        assert _pick_blocks(2048, 2048, 512, 512, jnp.bfloat16) == (512, 512)
        assert _pick_blocks(1536, 1536, 1024, 1024, jnp.float32) == (768, 768)
        with pytest.raises(ValueError, match="length-1031"):
            _pick_blocks(1031, 1031, 512, 512, jnp.float32)

    def test_aligned_divisor_property(self):
        for dtype, align in ((jnp.float32, 8), (jnp.int8, 32)):
            for L in range(1, 160):
                for req in (1, 8, 13, 32, 128):
                    try:
                        got = pick_block(L, req, dtype)
                    except ValueError:
                        assert L > req and not any(
                            L % d == 0
                            for d in range(align, req + 1, align)
                        ), (L, req)
                        continue
                    assert L % got == 0 and got <= max(req, 1), (L, req)
                    assert got == L or got % align == 0, (L, req, got)

    def test_engine_rejects_untileable_lane_at_construction(self):
        spec = SPEC._replace(total_len=2064)
        with pytest.raises(ValueError, match="total_len 2064"):
            resolve_engine_knobs(
                spec, decode_attn="flash", kv_dtype="int8"
            )
        knobs = resolve_engine_knobs(spec, decode_attn="flash")
        assert knobs["decode_block_k"] == 48

    def test_flash_matches_reference_on_non_divisible_L(self):
        """The fallback path computes the same attention: L=48 keys,
        block request 32 → effective 24, two banded blocks."""
        rng = np.random.default_rng(48)
        S, H, H_kv, Dh, L = 3, 4, 2, 8, 48
        q = jnp.asarray(rng.normal(size=(S, H, Dh)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(S, L, H_kv, Dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(S, L, H_kv, Dh)), jnp.float32)
        pos = jnp.asarray([0, 23, 47], jnp.int32)
        ref = decode_attention_reference(q, k, v, pos)
        out = flash_decode_attention(q, k, v, pos, block_k=32)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    def test_xprof_annotate_lands_in_ledger(self):
        """The engine's block_k annotation route: notes attached before
        OR after the compile both surface on the ledger record; a
        disabled profiler stays free (no state kept)."""
        from ddp_tpu.obs.xprof import Xprof

        xp = Xprof(enabled=True)
        xp.annotate("tune.probe", block_k_requested=32, block_k=24)
        f = xp.instrument(jax.jit(lambda x: x * 2), "tune.probe")
        f(jnp.ones((4,), jnp.float32))
        rec = [
            p for p in xp.ledger_records() if p["label"] == "tune.probe"
        ]
        assert rec and rec[0]["notes"]["block_k"] == 24
        xp.annotate("tune.probe", block_k=12)  # post-compile merge
        rec = [
            p for p in xp.ledger_records() if p["label"] == "tune.probe"
        ]
        assert rec[0]["notes"] == {"block_k_requested": 32, "block_k": 12}

        off = Xprof(enabled=False)
        off.annotate("x", a=1)
        assert off._notes == {}
