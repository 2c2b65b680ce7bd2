"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, rpca_admm
from repro.kernels.lora_matmul import _rank_pad


def arr(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5), jnp.bfloat16: dict(atol=0.15, rtol=0.1)}


class TestSoftThreshold:
    @pytest.mark.parametrize("shape", [(8, 128), (300, 70), (1, 1), (257, 129), (1000, 5)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, shape, dtype, rng):
        x = arr(rng, shape, dtype)
        for t in (0.0, 0.3, 2.0):
            got = ops.soft_threshold(x, t)
            want = ref.soft_threshold_ref(x, jnp.asarray(t, dtype))
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
            )

    def test_3d_input(self, rng):
        x = arr(rng, (4, 33, 65), jnp.float32)
        got = ops.soft_threshold(x, 0.5)
        np.testing.assert_allclose(got, ref.soft_threshold_ref(x, 0.5), atol=1e-6)


class TestRPCAAdmmTail:
    """Fused ADMM elementwise tail vs the jnp oracle (interpret mode)."""

    def _inputs(self, rng, b, d, nc):
        m, l, y = (jnp.asarray(rng.normal(size=(b, d, nc)), jnp.float32) for _ in range(3))
        rho = jnp.asarray(rng.uniform(0.5, 2.0, b), jnp.float32)
        return m, l, y, rho, 1.0 / rho, rho * 0.1

    @pytest.mark.parametrize("b,d,nc", [(3, 64, 8), (5, 100, 12), (2, 300, 100), (1, 1, 1)])
    @pytest.mark.parametrize("block_vec", [32, 512])
    def test_sweep(self, b, d, nc, block_vec, rng):
        m, l, y, rho, mu, th = self._inputs(rng, b, d, nc)
        s, y_new, rsq = rpca_admm.admm_tail(
            m, l, y, rho, mu, th, block_vec=block_vec, interpret=True
        )
        s_w, y_w, rsq_w = ref.rpca_admm_tail_ref(m, l, y, rho, mu, th)
        np.testing.assert_allclose(s, s_w, atol=2e-6)
        np.testing.assert_allclose(y_new, y_w, atol=2e-6)
        np.testing.assert_allclose(rsq, rsq_w, rtol=1e-5)

    def test_blockwise_residual_accumulation(self, rng):
        """Partial sums across vec blocks must total the full residual norm,
        independent of the tiling."""
        m, l, y, rho, mu, th = self._inputs(rng, 2, 250, 6)
        _, _, r_small = rpca_admm.admm_tail(m, l, y, rho, mu, th, block_vec=16, interpret=True)
        _, _, r_full = rpca_admm.admm_tail(m, l, y, rho, mu, th, block_vec=512, interpret=True)
        np.testing.assert_allclose(r_small, r_full, rtol=1e-5)

    def test_padded_rows_are_inert(self, rng):
        """Zero rows (bucket padding) produce zero S/Y rows and no residual."""
        m, l, y, rho, mu, th = self._inputs(rng, 2, 40, 6)
        pad = lambda t: jnp.pad(t, ((0, 0), (0, 24), (0, 0)))
        s, y_new, rsq = rpca_admm.admm_tail(pad(m), pad(l), pad(y), rho, mu, th, interpret=True)
        _, _, rsq_ref = ref.rpca_admm_tail_ref(m, l, y, rho, mu, th)
        assert float(jnp.abs(s[:, 40:]).max()) == 0.0
        assert float(jnp.abs(y_new[:, 40:]).max()) == 0.0
        np.testing.assert_allclose(rsq, rsq_ref, rtol=1e-5)

    def test_client_mask_blanks_inactive_columns(self, rng):
        """Masked client columns are forced to zero and excluded from the
        blockwise residual sums (shape-static partial participation)."""
        m, l, y, rho, mu, th = self._inputs(rng, 2, 40, 8)
        mask = jnp.asarray([1, 1, 1, 1, 1, 0, 0, 0], jnp.float32)
        s, y_new, rsq = rpca_admm.admm_tail(m, l, y, rho, mu, th, mask=mask, interpret=True)
        s_w, y_w, rsq_w = ref.rpca_admm_tail_ref(m, l, y, rho, mu, th, mask=mask)
        np.testing.assert_allclose(s, s_w, atol=2e-6)
        np.testing.assert_allclose(y_new, y_w, atol=2e-6)
        np.testing.assert_allclose(rsq, rsq_w, rtol=1e-5)
        assert float(jnp.abs(s[:, :, 5:]).max()) == 0.0
        assert float(jnp.abs(y_new[:, :, 5:]).max()) == 0.0
        # residual sums match the dense sub-cohort tail on the active columns
        _, _, rsq_dense = ref.rpca_admm_tail_ref(
            m[:, :, :5], l[:, :, :5], y[:, :, :5], rho, mu, th
        )
        np.testing.assert_allclose(rsq, rsq_dense, rtol=1e-5)


class TestLoraMatmul:
    @pytest.mark.parametrize(
        "m,k,n,r",
        [
            (64, 64, 64, 4),
            (200, 192, 160, 8),
            (16, 512, 48, 16),
            (130, 70, 90, 32),
            # rank not a multiple of the 128 lane width, and rank > 128:
            # exercises the zero-pad of A/B up to the padded rank tile.
            (64, 96, 72, 100),
            (40, 130, 90, 160),
        ],
    )
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, m, k, n, r, dtype, rng):
        x, w = arr(rng, (m, k), dtype), arr(rng, (k, n), dtype)
        a, b = arr(rng, (k, r), dtype), arr(rng, (r, n), dtype)
        got = ops.lora_matmul(x, w, a, b, 1.7)
        want = ref.lora_matmul_ref(
            x.astype(jnp.float32), w.astype(jnp.float32),
            a.astype(jnp.float32), b.astype(jnp.float32), 1.7,
        )
        scale = float(jnp.max(jnp.abs(want))) + 1e-6
        err = float(jnp.max(jnp.abs(np.asarray(got, np.float32) - want))) / scale
        assert err < (2e-5 if dtype == jnp.float32 else 0.05), err

    def test_zero_lora_is_base_matmul(self, rng):
        x, w = arr(rng, (32, 48), jnp.float32), arr(rng, (48, 24), jnp.float32)
        a = arr(rng, (48, 8), jnp.float32)
        b = jnp.zeros((8, 24), jnp.float32)
        np.testing.assert_allclose(ops.lora_matmul(x, w, a, b, 9.0), x @ w, atol=2e-5)

    def test_batched_leading_dims(self, rng):
        x = arr(rng, (2, 5, 48), jnp.float32)
        w, a, b = arr(rng, (48, 24), jnp.float32), arr(rng, (48, 4), jnp.float32), arr(rng, (4, 24), jnp.float32)
        got = ops.lora_matmul(x, w, a, b, 1.0)
        assert got.shape == (2, 5, 24)
        np.testing.assert_allclose(got, ref.lora_matmul_ref(x, w, a, b, 1.0), atol=2e-5)

    def test_scale_zero_is_base_matmul(self, rng):
        x, w = arr(rng, (32, 48), jnp.float32), arr(rng, (48, 24), jnp.float32)
        a, b = arr(rng, (48, 8), jnp.float32), arr(rng, (8, 24), jnp.float32)
        np.testing.assert_allclose(ops.lora_matmul(x, w, a, b, 0.0), x @ w, atol=2e-5)

    def test_remainder_tiles_all_dims(self, rng):
        """M, N and K all leave remainder tiles simultaneously."""
        m, k, n, r = 129, 513, 130, 8
        x, w = arr(rng, (m, k), jnp.float32), arr(rng, (k, n), jnp.float32)
        a, b = arr(rng, (k, r), jnp.float32), arr(rng, (r, n), jnp.float32)
        got = np.asarray(ops.lora_matmul(x, w, a, b, 1.3), np.float64)
        # The kernel sums K in a (512 + zero-padded remainder) tiling, one XLA
        # dot in another order, so the two differ by f32 rounding (~3e-4 on
        # outputs of ~400), above any fixed atol.  Hold the kernel to the f32
        # summation bound instead: within eps * sum|terms| of the float64
        # product, elementwise (a tiling bug is off by whole terms).
        x, w, a, b = (np.asarray(t, np.float64) for t in (x, w, a, b))
        exact = x @ w + 1.3 * (x @ a) @ b
        mag = np.abs(x) @ np.abs(w) + 1.3 * (np.abs(x) @ np.abs(a)) @ np.abs(b)
        assert np.all(np.abs(got - exact) <= np.finfo(np.float32).eps * mag)


class TestGatheredLoraMatmul:
    """Multi-adapter gathered matmul vs the grouped-by-adapter XLA oracle.

    fp32 comparisons are BITWISE: both impls share the compiled oracle's
    accumulation order per row, so any index-plumbing bug (wrong slot, wrong
    unsort) shows up as an exact mismatch, not a tolerance question.  The
    oracle must itself be jitted — eager vs jit of the same reference differ
    in the final fused add chain.  The Pallas kernel zero-pads the rank to
    the 128 lane width, and an XLA dot over 128 terms sums in another order
    than one over 8, so its oracle gets the same zero-padded pools: same
    values, same op order.
    """

    S, M, K, N, R = 5, 37, 48, 33, 8

    def _pools(self, rng, dtype=jnp.float32, s=None, k=None, n=None, r=None):
        s, k, n, r = s or self.S, k or self.K, n or self.N, r or self.R
        x = arr(rng, (self.M, k), dtype)
        w = arr(rng, (k, n), dtype)
        a_pool = arr(rng, (s, k, r), dtype)
        b_pool = arr(rng, (s, r, n), dtype)
        return x, w, a_pool, b_pool

    def _index_cases(self, rng):
        m, s = self.M, self.S
        return {
            "permuted": rng.permutation(np.arange(m) % s),
            "duplicate": np.repeat(rng.integers(0, s, (m + 3) // 4), 4)[:m],
            "all_same": np.full(m, 2),
            "masked": rng.integers(-1, s, m),  # -1 = no adapter
        }

    @pytest.mark.parametrize("impl,interpret", [("pallas", True), ("xla", None)])
    def test_bitwise_vs_grouped_oracle(self, impl, interpret, rng):
        x, w, a_pool, b_pool = self._pools(rng)
        ref_jit = jax.jit(ref.gathered_lora_matmul_ref)
        a_ref, b_ref = a_pool, b_pool
        if impl == "pallas":
            r_pad = _rank_pad(self.R)
            a_ref = jnp.pad(a_pool, ((0, 0), (0, 0), (0, r_pad)))
            b_ref = jnp.pad(b_pool, ((0, 0), (0, r_pad), (0, 0)))
        for name, idx in self._index_cases(rng).items():
            row_slot = jnp.asarray(idx, jnp.int32)
            got = ops.gathered_lora_matmul(
                x, w, a_pool, b_pool, row_slot, 1.7, impl=impl, interpret=interpret
            )
            want = ref_jit(x, w, a_ref, b_ref, row_slot, 1.7)
            assert bool(jnp.all(got == want)), f"{impl}/{name}: not bitwise"

    @pytest.mark.parametrize("impl,interpret", [("pallas", True), ("xla", None)])
    def test_masked_rows_get_base_only(self, impl, interpret, rng):
        x, w, a_pool, b_pool = self._pools(rng)
        row_slot = jnp.asarray(
            [-1 if i % 3 == 0 else i % self.S for i in range(self.M)], jnp.int32
        )
        got = ops.gathered_lora_matmul(
            x, w, a_pool, b_pool, row_slot, 2.0, impl=impl, interpret=interpret
        )
        base = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
        masked = np.asarray(row_slot) < 0
        np.testing.assert_allclose(
            np.asarray(got)[masked], np.asarray(base)[masked], atol=2e-5
        )
        assert float(jnp.max(jnp.abs(got[~masked] - base[~masked]))) > 1e-3

    def test_request_level_slots_3d(self, rng):
        """(B,) slots broadcast over (B, S, K) activations — the serving path."""
        b, s_len = 6, 7
        x = arr(rng, (b, s_len, self.K), jnp.float32)
        w = arr(rng, (self.K, self.N), jnp.float32)
        a_pool = arr(rng, (self.S, self.K, self.R), jnp.float32)
        b_pool = arr(rng, (self.S, self.R, self.N), jnp.float32)
        req_slot = jnp.asarray([0, 3, 3, 1, 4, 0], jnp.int32)
        got = ops.gathered_lora_matmul(x, w, a_pool, b_pool, req_slot, 1.0, impl="xla")
        assert got.shape == (b, s_len, self.N)
        for i in range(b):
            want = ref.lora_matmul_ref(
                x[i], w, a_pool[req_slot[i]], b_pool[req_slot[i]], 1.0
            )
            np.testing.assert_allclose(got[i], want, atol=3e-5, rtol=2e-5)

    def test_matches_per_slot_single_adapter_kernel(self, rng):
        """Each row's result equals running the single-adapter kernel with
        that row's adapter."""
        x, w, a_pool, b_pool = self._pools(rng)
        row_slot = jnp.asarray(np.arange(self.M) % self.S, jnp.int32)
        got = ops.gathered_lora_matmul(x, w, a_pool, b_pool, row_slot, 1.0, impl="xla")
        for s in range(self.S):
            rows = np.asarray(row_slot) == s
            want = ops.lora_matmul(x, w, a_pool[s], b_pool[s], 1.0, interpret=True)
            np.testing.assert_allclose(
                np.asarray(got)[rows], np.asarray(want)[rows], atol=3e-5, rtol=2e-5
            )

    def test_bf16(self, rng):
        x, w, a_pool, b_pool = self._pools(rng, dtype=jnp.bfloat16)
        row_slot = jnp.asarray(np.arange(self.M) % self.S, jnp.int32)
        got = ops.gathered_lora_matmul(x, w, a_pool, b_pool, row_slot, 1.0, impl="xla")
        want = ref.gathered_lora_matmul_ref(x, w, a_pool, b_pool, row_slot, 1.0)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=0.2, rtol=0.02,
        )

    def test_max_segments_invariance(self, rng):
        """Tightening the segment bound (serving passes n_requests) must not
        change results, only the tile layout."""
        x, w, a_pool, b_pool = self._pools(rng)
        row_slot = jnp.asarray(np.arange(self.M) % 3, jnp.int32)  # 3 distinct
        full = ops.gathered_lora_matmul(x, w, a_pool, b_pool, row_slot, 1.0, impl="xla")
        tight = ops.gathered_lora_matmul(
            x, w, a_pool, b_pool, row_slot, 1.0, impl="xla", max_segments=3
        )
        assert bool(jnp.all(full == tight))

    def test_bad_inputs_raise(self, rng):
        x, w, a_pool, b_pool = self._pools(rng)
        row_slot = jnp.asarray(np.arange(self.M) % self.S, jnp.int32)
        with pytest.raises(ValueError):
            ops.gathered_lora_matmul(x, w, a_pool, b_pool, row_slot, impl="nope")
        with pytest.raises(ValueError):
            ops.gathered_lora_matmul(
                x, w, a_pool, b_pool, jnp.zeros((2, 2), jnp.int32)
            )


class TestLocalAttention:
    @pytest.mark.parametrize("s,window", [(128, 0), (128, 32), (200, 64), (100, 16), (64, 128)])
    def test_sweep(self, s, window, rng):
        q, k, v = (arr(rng, (4, s, 32), jnp.float32) for _ in range(3))
        got = ops.local_attention(q, k, v, window=window, causal=True)
        want = ref.local_attention_ref(q, k, v, window=window, causal=True)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)

    def test_bf16(self, rng):
        q, k, v = (arr(rng, (2, 128, 64), jnp.bfloat16) for _ in range(3))
        got = ops.local_attention(q, k, v, window=32)
        want = ref.local_attention_ref(q, k, v, window=32)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=0.05
        )

    def test_4d_layout(self, rng):
        q = arr(rng, (2, 96, 4, 16), jnp.float32)
        k, v = arr(rng, (2, 96, 4, 16), jnp.float32), arr(rng, (2, 96, 4, 16), jnp.float32)
        got = ops.local_attention(q, k, v, window=24)
        assert got.shape == q.shape
        per_head = ref.local_attention_ref(
            jnp.transpose(q, (0, 2, 1, 3)).reshape(8, 96, 16),
            jnp.transpose(k, (0, 2, 1, 3)).reshape(8, 96, 16),
            jnp.transpose(v, (0, 2, 1, 3)).reshape(8, 96, 16),
            window=24,
        )
        np.testing.assert_allclose(
            jnp.transpose(got, (0, 2, 1, 3)).reshape(8, 96, 16), per_head, atol=2e-5
        )

    def test_matches_model_flash_path(self, rng):
        """Kernel vs the model's jnp flash attention (mesh execution path)."""
        from repro.models.attention import flash_attention

        b, s, h, d = 2, 256, 2, 16
        q = arr(rng, (b, s, h, 1, d), jnp.float32)
        k, v = arr(rng, (b, s, h, d), jnp.float32), arr(rng, (b, s, h, d), jnp.float32)
        flash = flash_attention(q, k, v, causal=True, window=64, block_q=64, block_k=64)
        kern = ops.local_attention(q[:, :, :, 0], k, v, window=64)
        np.testing.assert_allclose(flash[:, :, :, 0], kern, atol=3e-5, rtol=1e-4)


class TestSSDScan:
    """The SSD chunk kernels (``kernels/ssd_scan.py``), through the model's
    glue ``models/ssd.py::ssd_pallas`` or called directly, against the
    sequential reference and ``ssd_chunked``.  Matmuls at "highest": at the
    default precision the kernels round their operands to bf16, as XLA's TPU
    backend does."""

    @pytest.fixture(autouse=True)
    def highest(self):
        with jax.default_matmul_precision("highest"):
            yield

    @staticmethod
    def inputs(rng, bsz, s, h, p, n):
        x = arr(rng, (bsz, s, h, p), jnp.float32)
        dt = jnp.abs(arr(rng, (bsz, s, h), jnp.float32)) * 0.1 + 0.01
        a_log = jnp.asarray(np.log(np.linspace(1.0, 4.0, h)), jnp.float32)
        return x, dt, a_log, arr(rng, (bsz, s, n), jnp.float32), arr(rng, (bsz, s, n), jnp.float32)

    @staticmethod
    def close(got, want, rel=2e-6):
        """Within ``rel`` of the largest entry of ``want``."""
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        np.testing.assert_allclose(got, want, rtol=0, atol=rel * np.max(np.abs(want)))

    @pytest.mark.parametrize("s,chunk", [(64, 16), (96, 32), (100, 32), (256, 256)])
    def test_sweep(self, s, chunk, rng):
        """Kernels vs the exact sequential scan, S a multiple of the chunk or not."""
        from repro.models.ssd import ssd_pallas

        bsz, h, p, n = 1, 2, 64, 8
        x, dt, a_log, bm, cm = self.inputs(rng, bsz, s, h, p, n)
        y, _ = ssd_pallas(x, dt, a_log, bm, cm, jnp.zeros((h,)), chunk)
        da = (dt * -jnp.exp(a_log)).transpose(0, 2, 1).reshape(bsz * h, s)
        xk = (x * dt[..., None]).transpose(0, 2, 1, 3).reshape(bsz * h, s, p)
        per_head = lambda m: jnp.broadcast_to(m[:, None], (bsz, h, s, n)).reshape(bsz * h, s, n)
        want = ref.ssd_scan_ref(xk, da, per_head(bm), per_head(cm), chunk)
        want = want.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(y, want, atol=5e-5, rtol=1e-3)

    def test_matches_model_ssd_chunked(self, rng):
        """``ssd_chunk_scan`` called directly: the model's chunk core from the
        dt-premultiplied x and the within-chunk cumulative log-decays."""
        from repro.kernels.ssd_scan import ssd_chunk_scan
        from repro.models.ssd import ssd_chunked

        bsz, s, h, p, n, chunk = 2, 64, 4, 32, 4, 16
        x, dt, a_log, bm, cm = self.inputs(rng, bsz, s, h, p, n)
        y_model, h_model = ssd_chunked(x, dt, a_log, bm, cm, jnp.zeros((h,)), chunk)
        da = (dt * -jnp.exp(a_log)).reshape(bsz, s // chunk, chunk, h)
        cum = jnp.cumsum(da, axis=2).reshape(bsz, s, h)
        xk = (x * dt[..., None]).reshape(bsz, s, h * p)
        y, h_final = ssd_chunk_scan(xk, cum, bm, cm, chunk=chunk)
        self.close(y.reshape(bsz, s, h, p), y_model)
        self.close(h_final.reshape(bsz, n, h, p).transpose(0, 2, 3, 1), h_model)

    @pytest.mark.parametrize("bsz,s,h,p,n,chunk", [
        (1, 100, 4, 64, 16, 32),  # S not a multiple of Q; 4 chunks; 2 head blocks of 2
        (2, 64, 8, 32, 8, 16),  # 2 head blocks of 4
        (1, 48, 3, 128, 8, 16),  # 3 head blocks of 1
    ])
    def test_forward_matches_ssd_chunked(self, bsz, s, h, p, n, chunk, rng):
        from repro.models.ssd import ssd_chunked, ssd_pallas

        args = self.inputs(rng, bsz, s, h, p, n) + (arr(rng, (h,), jnp.float32),)
        y, state = ssd_pallas(*args, chunk)
        y_want, state_want = ssd_chunked(*args, chunk)
        self.close(y, y_want)
        self.close(state, state_want)

    @pytest.mark.parametrize("bsz,s,h,p,n,chunk", [
        (1, 100, 4, 64, 16, 32),
        (2, 48, 8, 32, 8, 16),
    ])
    def test_grads_match_ssd_chunked(self, bsz, s, h, p, n, chunk, rng):
        """The custom_vjp's gradients in x, dt, A_log, B and C, through both
        outputs, against autodiff of ``ssd_chunked``."""
        from repro.models.ssd import ssd_chunked, ssd_pallas

        args = self.inputs(rng, bsz, s, h, p, n) + (arr(rng, (h,), jnp.float32),)
        wy = arr(rng, (bsz, s, h, p), jnp.float32)
        wh = arr(rng, (bsz, h, p, n), jnp.float32)

        def grads(core):
            def loss(*a):
                y, state = core(*a, chunk)
                return jnp.sum(y * wy) + jnp.sum(state * wh)
            return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)

        for got, want in zip(grads(ssd_pallas), grads(ssd_chunked)):
            self.close(got, want, rel=1e-5)

    def test_vmap_over_clients_under_checkpoint(self, rng):
        """As the local step runs the mixer: vmapped over clients, rematted."""
        from repro.models.ssd import ssd_chunked, ssd_pallas

        clients, s, h, p, n, chunk = 3, 64, 4, 32, 8, 16
        x, dt, a_log, bm, cm = self.inputs(rng, clients, s, h, p, n)
        d = arr(rng, (h,), jnp.float32)
        w = arr(rng, (clients, s, h, p), jnp.float32)

        def grads(core):
            def client(x, dt, bm, cm, a_log):
                y, _ = core(x[None], dt[None], a_log, bm[None], cm[None], d, chunk)
                return y[0]

            def loss(x, dt, bm, cm, a_log):
                y = jax.checkpoint(jax.vmap(client, in_axes=(0, 0, 0, 0, None)))(
                    x, dt, bm, cm, a_log)
                return jnp.sum(y * w)
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(x, dt, bm, cm, a_log)

        for got, want in zip(grads(ssd_pallas), grads(ssd_chunked)):
            self.close(got, want, rel=1e-5)

    def test_default_precision_is_one_bf16_pass(self, rng):
        """Outside "highest" the kernels' matmul operands are bf16, as a float32
        dot's at XLA's default precision on a TPU: within bf16 rounding of the
        float32 result, and not equal to it."""
        from repro.models.ssd import ssd_chunked, ssd_pallas

        args = self.inputs(rng, 1, 64, 2, 64, 16) + (jnp.zeros((2,)),)
        want, _ = ssd_chunked(*args, 16)
        with jax.default_matmul_precision("default"):
            got, _ = ssd_pallas(*args, 16)
        self.close(got, want, rel=2e-2)
        assert float(jnp.max(jnp.abs(got - want))) > 0.0

    @pytest.mark.parametrize("h,p,hb", [(128, 64, 2), (24, 64, 2), (16, 32, 4), (3, 128, 1),
                                        (2, 256, 1), (3, 64, None), (4, 96, None)])
    def test_heads_per_block(self, h, p, hb):
        from repro.kernels.ssd_scan import heads_per_block

        assert heads_per_block(h, p) == hb
