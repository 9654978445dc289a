"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref


@pytest.mark.parametrize("shape", [(3, 37), (500,), (256, 128), (7, 11, 13)])
@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_fisher_diag(rng, shape, momentum):
    g = jax.random.normal(rng, shape)
    f = jnp.abs(jax.random.normal(jax.random.fold_in(rng, 1), shape))
    out = ops.fisher_diag_update(f, g, momentum)
    exp = ref.fisher_diag_update_ref(g, f, momentum)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-6)


# ---------------------------------------------------------------------------
# fused masked optimizer update (masked_update kernel)
# ---------------------------------------------------------------------------

# non-tile-multiple shapes (incl. sub-tile remainders) exercise the wrapper's
# pad-to-tile path; (256, 128) is exactly one block
_UPD_SHAPES = [(3, 37), (500,), (256, 128), (257, 130), (7, 11, 13)]


def _upd_inputs(rng, shape, dtype, density):
    p = jax.random.normal(rng, shape, dtype)
    g = jax.random.normal(jax.random.fold_in(rng, 1), shape, dtype)
    mask = (
        None
        if density is None
        else (jax.random.uniform(jax.random.fold_in(rng, 2), shape) < density).astype(
            jnp.float32
        )
    )
    return p, g, mask


@pytest.mark.parametrize("shape", _UPD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_masked_sgd_kernel(rng, shape, dtype, momentum):
    tol = dict(atol=1e-6, rtol=1e-6) if dtype == jnp.float32 else dict(atol=2e-2, rtol=2e-2)
    for density in (None, 0.0, 0.5, 1.0):
        for active in (None, 1.0, 0.0):
            p, g, mask = _upd_inputs(rng, shape, dtype, density)
            mu = (
                jax.random.normal(jax.random.fold_in(rng, 3), shape, dtype)
                if momentum
                else None
            )
            new_p, new_mu = ops_masked_sgd_2d(p, g, mu, mask, active, momentum)
            exp_p, exp_mu = ref.masked_sgd_update_ref(
                p, g, mu, mask, 0.1, momentum=momentum, active=active
            )
            np.testing.assert_allclose(
                np.asarray(new_p, np.float32), np.asarray(exp_p, np.float32), **tol
            )
            if momentum:
                np.testing.assert_allclose(
                    np.asarray(new_mu, np.float32), np.asarray(exp_mu, np.float32), **tol
                )


def ops_masked_sgd_2d(p, g, mu, mask, active, momentum):
    """Force the kernel path through the public tree-level wrapper."""
    state = {"mu": {"w": mu}} if momentum else {}
    new_p, new_st = ops.masked_sgd_update(
        {"w": g}, state, {"w": p}, 0.1,
        {"w": mask} if mask is not None else None, active,
        momentum=momentum, use_kernel=True,
    )
    return new_p["w"], (new_st["mu"]["w"] if momentum else None)


@pytest.mark.parametrize("shape", _UPD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_adamw_kernel(rng, shape, dtype):
    tol = dict(atol=1e-6, rtol=1e-6) if dtype == jnp.float32 else dict(atol=2e-2, rtol=2e-2)
    for density in (None, 0.0, 0.5, 1.0):
        for active in (None, 1.0, 0.0):
            p, g, mask = _upd_inputs(rng, shape, dtype, density)
            m = jax.random.normal(jax.random.fold_in(rng, 3), shape, dtype) * 0.1
            v = jnp.abs(jax.random.normal(jax.random.fold_in(rng, 4), shape, dtype)) * 0.1
            state = {"m": {"w": m}, "v": {"w": v}, "t": jnp.int32(3)}
            new_p, new_st = ops.masked_adamw_update(
                {"w": g}, state, {"w": p}, 0.01,
                {"w": mask} if mask is not None else None, active,
                wd=0.01, use_kernel=True,
            )
            # oracle shares the wrapper's externally-advanced step counter
            t = 3 + (1 if active is None else int(active != 0))
            mhat = 1.0 / (1.0 - 0.9**t)
            vhat = 1.0 / (1.0 - 0.999**t)
            exp_p, exp_m, exp_v = ref.masked_adamw_update_ref(
                p, g, m, v, mask, 0.01, mhat, vhat, wd=0.01, active=active
            )
            assert int(new_st["t"]) == t
            for got, exp in [
                (new_p["w"], exp_p), (new_st["m"]["w"], exp_m), (new_st["v"]["w"], exp_v)
            ]:
                np.testing.assert_allclose(
                    np.asarray(got, np.float32), np.asarray(exp, np.float32), **tol
                )


def test_masked_update_kernel_under_vmap(rng):
    """The round engines call the fused update inside vmap-over-clients with
    a per-client ``active`` scalar — the batched pallas_call must agree with
    the per-client oracle."""
    k, shape = 3, (256, 128)
    p = jax.random.normal(rng, (k,) + shape)
    g = jax.random.normal(jax.random.fold_in(rng, 1), (k,) + shape)
    mu = jax.random.normal(jax.random.fold_in(rng, 2), (k,) + shape)
    mask = (jax.random.uniform(jax.random.fold_in(rng, 3), (k,) + shape) > 0.5).astype(
        jnp.float32
    )
    active = jnp.array([1.0, 0.0, 1.0])

    def one(p_, g_, mu_, mk_, a):
        new_p, new_st = ops.masked_sgd_update(
            {"w": g_}, {"mu": {"w": mu_}}, {"w": p_}, 0.1, {"w": mk_}, a,
            momentum=0.9, use_kernel=True,
        )
        return new_p["w"], new_st["mu"]["w"]

    got_p, got_mu = jax.jit(jax.vmap(one))(p, g, mu, mask, active)
    for i in range(k):
        exp_p, exp_mu = ref.masked_sgd_update_ref(
            p[i], g[i], mu[i], mask[i], 0.1, momentum=0.9, active=active[i]
        )
        np.testing.assert_allclose(np.asarray(got_p[i]), np.asarray(exp_p), atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_mu[i]), np.asarray(exp_mu), atol=1e-6)


@pytest.mark.parametrize("M,K,N,r", [(128, 512, 128, 8), (200, 300, 250, 4), (256, 1024, 384, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_lora(rng, M, K, N, r, dtype):
    x = jax.random.normal(rng, (M, K), dtype)
    a = jax.random.normal(jax.random.fold_in(rng, 1), (K, r), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(rng, 2), (r, N), jnp.float32)
    mask = (jax.random.uniform(jax.random.fold_in(rng, 3), (N,)) > 0.5).astype(jnp.float32)
    y = ops.sparse_lora_apply(x, a, b, mask, 2.0)
    ye = ref.sparse_lora_matmul_ref(x, a, b, mask, 2.0)
    tol = 1e-3 if dtype == jnp.float32 else 5e-2  # f32: K=1024 accumulation
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(ye, np.float32), rtol=tol, atol=tol
    )


def test_sparse_lora_masked_columns_zero(rng):
    x = jax.random.normal(rng, (128, 512))
    a = jax.random.normal(rng, (512, 8))
    b = jax.random.normal(rng, (8, 128))
    mask = jnp.zeros((128,)).at[:64].set(1.0)
    y = ops.sparse_lora_apply(x, a, b, mask)
    assert float(jnp.max(jnp.abs(y[:, 64:]))) == 0.0  # frozen neurons: no delta


@pytest.mark.parametrize(
    "M,K,N,r,A",
    [
        (128, 512, 128, 8, 1),  # tile-exact, single adapter ≡ unbatched
        (128, 512, 128, 4, 4),  # tile-exact, multi-adapter
        (64, 96, 80, 4, 3),  # every dim off-tile
        (200, 1024, 250, 16, 2),  # mixed off-tile, multi-k-step
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_sparse_lora(rng, M, K, N, r, A, dtype):
    x = jax.random.normal(rng, (M, K), dtype)
    idx = jax.random.randint(jax.random.fold_in(rng, 1), (M,), 0, A, jnp.int32)
    a = jax.random.normal(jax.random.fold_in(rng, 2), (A, K, r), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(rng, 3), (A, r, N), jnp.float32)
    # per-adapter keep ratios sweep ρ: adapter i keeps ~ (i+1)/(A+1) of columns
    u = jax.random.uniform(jax.random.fold_in(rng, 4), (A, N))
    mask = (u < (jnp.arange(1, A + 1, dtype=jnp.float32)[:, None] / (A + 1))).astype(
        jnp.float32
    )
    y = ops.batched_sparse_lora_apply(x, idx, a, b, mask, 2.0)
    ye = ref.batched_sparse_lora_matmul_ref(x, idx, a, b, mask, 2.0)
    tol = 1e-3 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(ye, np.float32), rtol=tol, atol=tol
    )
    if A == 1:
        ys = ref.sparse_lora_matmul_ref(x, a[0], b[0], mask[0], 2.0)
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(ys, np.float32), rtol=tol, atol=tol
        )


def test_batched_sparse_lora_leading_dims(rng):
    # (B, S, K) activations with a (B, S) per-row index, as used in serving
    B, S, K, N, r, A = 2, 32, 96, 80, 4, 3
    x = jax.random.normal(rng, (B, S, K))
    idx = jnp.broadcast_to(jnp.array([0, 2], jnp.int32)[:, None], (B, S))
    a = jax.random.normal(jax.random.fold_in(rng, 1), (A, K, r))
    b = jax.random.normal(jax.random.fold_in(rng, 2), (A, r, N))
    mask = jnp.ones((A, N))
    y = ops.batched_sparse_lora_apply(x, idx, a, b, mask)
    ye = ref.batched_sparse_lora_matmul_ref(
        x.reshape(-1, K), idx.reshape(-1), a, b, mask
    ).reshape(B, S, N)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("M,K,N,r", [(128, 512, 256, 8), (64, 96, 200, 4)])
@pytest.mark.parametrize("rho", [0.0, 0.25, 0.5])
def test_sparse_lora_packed(rng, M, K, N, r, rho):
    x = jax.random.normal(rng, (M, K))
    a = jax.random.normal(jax.random.fold_in(rng, 1), (K, r))
    b = jax.random.normal(jax.random.fold_in(rng, 2), (r, N))
    keep = int(round(rho * N))
    perm = jax.random.permutation(jax.random.fold_in(rng, 3), N)
    mask = jnp.zeros((N,)).at[perm[:keep]].set(1.0)
    y = ops.sparse_lora_apply_packed(x, a, b, mask, 2.0)
    ye = ref.sparse_lora_matmul_ref(x, a, b, mask, 2.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), rtol=1e-3, atol=1e-3)
    # the packed path's matmul only ever sees the kept columns
    if keep:
        yp = ref.sparse_lora_matmul_packed_ref(x, a, b[:, perm[:keep]], 2.0)
        np.testing.assert_allclose(
            np.asarray(y[:, perm[:keep]]), np.asarray(yp), rtol=1e-3, atol=1e-3
        )


@pytest.mark.parametrize("S,H,KVH,D", [(128, 4, 4, 64), (256, 4, 2, 64), (256, 8, 1, 128)])
@pytest.mark.parametrize("window", [None, 128])
def test_flash_attention(rng, S, H, KVH, D, window):
    B = 2
    q = jax.random.normal(rng, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, KVH, D))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, KVH, D))
    out = ops.flash_attention(q, k, v, causal=True, window=window)
    # oracle via the folded ref
    G = H // KVH
    kf = jnp.repeat(k, G, axis=2) if G > 1 else k
    vf = jnp.repeat(v, G, axis=2) if G > 1 else v
    exp = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3).reshape(B * H, S, D),
        kf.transpose(0, 2, 1, 3).reshape(B * H, S, D),
        vf.transpose(0, 2, 1, 3).reshape(B * H, S, D),
        causal=True, window=window,
    ).reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3, atol=2e-3)


def test_flash_matches_model_blockwise(rng):
    from repro.models.attention import blockwise_attention

    B, S, H, KVH, D = 2, 256, 4, 2, 64
    q = jax.random.normal(rng, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, KVH, D))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, KVH, D))
    a = ops.flash_attention(q, k, v, causal=True)
    b = blockwise_attention(q, k, v, causal=True, q_block=64, kv_block=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("Q,hd,N", [(128, 64, 32), (128, 128, 128), (64, 32, 16)])
def test_ssd_chunk(rng, Q, hd, N):
    G = 4
    x = jax.random.normal(rng, (G, Q, hd))
    a = -jnp.abs(jax.random.normal(jax.random.fold_in(rng, 1), (G, 1, Q))) * 0.1
    b = jax.random.normal(jax.random.fold_in(rng, 2), (G, Q, N))
    c = jax.random.normal(jax.random.fold_in(rng, 3), (G, Q, N))
    y = ops.ssd_chunk_intra(x, a, b, c)
    ye = ref.ssd_chunk_intra_ref(x, a, b, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), rtol=1e-4, atol=1e-4)


def test_ssd_chunk_matches_model_path(rng):
    """Kernel intra-chunk == ssd_chunked with a single chunk (zero init state)."""
    from repro.models.ssm import ssd_chunked

    B, Q, nh, hd, N = 2, 64, 2, 32, 16
    x = jax.random.normal(rng, (B, Q, nh, hd))
    a = -jnp.abs(jax.random.normal(jax.random.fold_in(rng, 1), (B, Q, nh))) * 0.1
    b = jax.random.normal(jax.random.fold_in(rng, 2), (B, Q, N))
    c = jax.random.normal(jax.random.fold_in(rng, 3), (B, Q, N))
    y_model, _ = ssd_chunked(x, a, b, c, chunk=Q)
    # kernel layout: (G=B*nh, Q, hd); B/C shared across heads
    xg = x.transpose(0, 2, 1, 3).reshape(B * nh, Q, hd)
    ag = a.transpose(0, 2, 1).reshape(B * nh, 1, Q)
    bg = jnp.repeat(b[:, None], nh, 1).reshape(B * nh, Q, N)
    cg = jnp.repeat(c[:, None], nh, 1).reshape(B * nh, Q, N)
    y_kernel = ops.ssd_chunk_intra(xg, ag, bg, cg).reshape(B, nh, Q, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(y_model), np.asarray(y_kernel), rtol=1e-4, atol=1e-4
    )


# ---------------------------------------------------------------------------
# platform-aware interpret default
# ---------------------------------------------------------------------------


def test_resolve_interpret(monkeypatch):
    """Explicit flag > platform default; no environment variable can force
    the interpreter onto a TPU, and no entry point defaults to it."""
    import importlib
    import inspect

    from repro.kernels.sparse_lora import resolve_interpret

    # modules by path: the package re-exports functions under the same names
    compress, fisher_diag, flash_attention, masked_update, sparse_lora, ssd_chunk = (
        importlib.import_module(f"repro.kernels.{m}")
        for m in (
            "compress", "fisher_diag", "flash_attention", "masked_update",
            "sparse_lora", "ssd_chunk",
        )
    )

    # explicit always wins
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    # platform default: this suite runs on CPU, so interpret
    assert jax.default_backend() != "tpu"
    assert resolve_interpret(None) is True
    # ... and compiled Mosaic on a TPU backend, whatever the environment says
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(sparse_lora.jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    assert resolve_interpret(True) is True

    # every kernel entry point leaves the choice to the platform
    entry_points = [
        compress.fake_compress_2d,
        fisher_diag.fisher_diag_update_2d,
        flash_attention.flash_attention_bhsd,
        masked_update.masked_sgd_update_2d,
        masked_update.masked_adamw_update_2d,
        sparse_lora.sparse_lora_matmul,
        sparse_lora.sparse_lora_matmul_packed,
        sparse_lora.batched_sparse_lora_matmul,
        ssd_chunk.ssd_chunk_intra_kernel,
    ]
    for fn in entry_points:
        assert inspect.signature(fn).parameters["interpret"].default is None, fn
