"""Public jit'd wrappers around the Pallas kernels.

Each wrapper handles padding/reshaping to kernel tile constraints and falls
back to the oracle for shapes below one tile. Interpret mode follows the
platform (``kernels.sparse_lora.resolve_interpret``): kernels interpret
everywhere except on a TPU backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import compress as _cp
from repro.kernels import fisher_diag as _fd
from repro.kernels import flash_attention as _fa
from repro.kernels import masked_update as _mu
from repro.kernels import ref as _ref
from repro.kernels import sparse_lora as _sl
from repro.kernels import ssd_chunk as _sc

# leaves below one (BLOCK_ROWS, BLOCK_COLS) tile take the oracle fallback in
# the masked-update wrappers (padding a 64-element LoRA leaf up to a 32k tile
# would invert the bandwidth win); use_kernel=True/False overrides per call
MIN_KERNEL_LEAF = _mu.BLOCK_ROWS * _mu.BLOCK_COLS


def _pad_to(x: jax.Array, axis: int, multiple: int):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


@functools.partial(jax.jit, static_argnames=("momentum",))
def fisher_diag_update(fim, g, momentum: float = 0.9):
    """Momentum diag-FIM update over an arbitrary pytree (leaf-wise kernel)."""

    def one(f_leaf, g_leaf):
        flat = g_leaf.reshape(-1)
        n = flat.shape[0]
        cols = _fd.BLOCK_COLS
        rows_needed = -(-n // cols)
        rows = max(_fd.BLOCK_ROWS, -(-rows_needed // _fd.BLOCK_ROWS) * _fd.BLOCK_ROWS)
        padded = rows * cols
        g2 = jnp.pad(flat, (0, padded - n)).reshape(rows, cols)
        f2 = jnp.pad(f_leaf.reshape(-1), (0, padded - n)).reshape(rows, cols)
        out = _fd.fisher_diag_update_2d(g2, f2, momentum)
        return out.reshape(-1)[:n].reshape(g_leaf.shape)

    return jax.tree.map(one, fim, g)


@functools.partial(jax.jit, static_argnames=("scale",))
def sparse_lora_apply(x, a, b, mask, scale: float = 1.0):
    """y = (x @ a) @ (b ⊙ mask) · scale. x (..., K); a (K, r); b (r, N)."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    r, N = b.shape
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    if M % _sl.BM or N % _sl.BN or K % _sl.BK:
        # pad to tiles
        x2, _ = _pad_to(x2, 0, _sl.BM)
        x2, _ = _pad_to(x2, 1, _sl.BK)
        a_p, _ = _pad_to(a, 0, _sl.BK)
        b_p, _ = _pad_to(b, 1, _sl.BN)
        m_p, _ = _pad_to(mask, 0, _sl.BN)
        y = _sl.sparse_lora_matmul(x2, a_p, b_p, m_p, scale)
        y = y[:M, :N]
    else:
        y = _sl.sparse_lora_matmul(x2, a, b, mask, scale)
    return y.reshape(*lead, N)


@functools.partial(jax.jit, static_argnames=("scale",))
def batched_sparse_lora_apply(x, idx, a, b, mask, scale: float = 1.0):
    """Multi-adapter apply: ``y[m] = x[m] @ a[idx[m]] @ (b[idx[m]] ⊙
    mask[idx[m]]) · scale``. x (..., K); idx (...,); a (A, K, r);
    b (A, r, N); mask (A, N)."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = b.shape[2]
    x2 = x.reshape(-1, K)
    idx2 = idx.reshape(-1).astype(jnp.int32)
    M = x2.shape[0]
    if M % _sl.BM or N % _sl.BN or K % _sl.BK:
        x2, _ = _pad_to(x2, 0, _sl.BM)
        x2, _ = _pad_to(x2, 1, _sl.BK)
        # padded rows read adapter 0's weights against all-zero x rows → 0
        idx2, _ = _pad_to(idx2, 0, _sl.BM)
        a_p, _ = _pad_to(a, 1, _sl.BK)
        b_p, _ = _pad_to(b, 2, _sl.BN)
        m_p, _ = _pad_to(mask, 1, _sl.BN)
        y = _sl.batched_sparse_lora_matmul(x2, idx2, a_p, b_p, m_p, scale)
        y = y[:M, :N]
    else:
        y = _sl.batched_sparse_lora_matmul(x2, idx2, a, b, mask, scale)
    return y.reshape(*lead, N)


def sparse_lora_apply_packed(x, a, b, mask, scale: float = 1.0):
    """Gather-packed apply: identical result to :func:`sparse_lora_apply`,
    but the frozen columns of ``b`` never reach the matmul.

    ``mask`` must be CONCRETE (host-visible — the §4.3.2 neuron mask is fixed
    per cohort, so this holds everywhere it matters): the kept-column index
    set determines array shapes, so this wrapper is not itself jittable. The
    pack → rank-r matmul → scatter pipeline pays MXU work proportional to
    ``N_keep = mask.sum()`` instead of ``N`` — at ρ ≤ 0.5 that beats
    zero-multiplying frozen columns in-tile.
    """
    keep = np.flatnonzero(np.asarray(mask))
    lead = x.shape[:-1]
    N = b.shape[1]
    if keep.size == 0:
        return jnp.zeros((*lead, N), x.dtype)
    yp = _packed_matmul(x, a, b[:, keep], scale)
    return jnp.zeros((*lead, N), x.dtype).at[..., keep].set(yp)


@functools.partial(jax.jit, static_argnames=("scale",))
def _packed_matmul(x, a, b_packed, scale: float):
    lead = x.shape[:-1]
    K = x.shape[-1]
    Nk = b_packed.shape[1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    if M % _sl.BM or Nk % _sl.BN or K % _sl.BK:
        x2, _ = _pad_to(x2, 0, _sl.BM)
        x2, _ = _pad_to(x2, 1, _sl.BK)
        a_p, _ = _pad_to(a, 0, _sl.BK)
        b_p, _ = _pad_to(b_packed, 1, _sl.BN)
        y = _sl.sparse_lora_matmul_packed(x2, a_p, b_p, scale)
        y = y[:M, :Nk]
    else:
        y = _sl.sparse_lora_matmul_packed(x2, a, b_packed, scale)
    return y.reshape(*lead, Nk)


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, *, causal: bool = True, window=None):
    """GQA flash attention. q (B,S,H,D); k/v (B,S,KVH,D). Returns q-shaped."""
    B, S, H, D = q.shape
    KVH = k.shape[2]
    G = H // KVH
    # fold heads: broadcast kv across the group then flatten (B,H)
    kq = jnp.repeat(k, G, axis=2) if G > 1 else k
    vq = jnp.repeat(v, G, axis=2) if G > 1 else v
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = kq.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vf = vq.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    if S % _fa.QB:
        out = _ref.flash_attention_ref(qf, kf, vf, causal=causal, window=window)
    else:
        out = _fa.flash_attention_bhsd(qf, kf, vf, causal=causal, window=window)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


@jax.jit
def ssd_chunk_intra(x, a, b, c):
    """Intra-chunk SSD. x (G,Q,hd), a (G,1,Q), b/c (G,Q,N) -> (G,Q,hd) f32."""
    return _sc.ssd_chunk_intra_kernel(x, a, b, c)


# ---------------------------------------------------------------------------
# fused masked optimizer updates (drop-ins for repro.optim's update fns)
# ---------------------------------------------------------------------------


def _tile2d(x: jax.Array) -> jax.Array:
    """Flatten a leaf and pad it to a (BLOCK_ROWS·k, BLOCK_COLS) tile grid."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    cols = _mu.BLOCK_COLS
    rows_needed = -(-n // cols)
    rows = max(
        _mu.BLOCK_ROWS, -(-rows_needed // _mu.BLOCK_ROWS) * _mu.BLOCK_ROWS
    )
    return jnp.pad(flat, (0, rows * cols - n)).reshape(rows, cols)


def _untile(x2: jax.Array, like: jax.Array) -> jax.Array:
    return x2.reshape(-1)[: like.size].reshape(like.shape).astype(like.dtype)


def _use_kernel(n: int, use_kernel) -> bool:
    return (n >= MIN_KERNEL_LEAF) if use_kernel is None else bool(use_kernel)


def _scal_row(lr, active, mhat_scale=0.0, vhat_scale=0.0) -> jax.Array:
    """The kernels' (1, SCAL_WIDTH) traced-scalar row [lr, active, m̂, v̂]."""
    act = (
        jnp.float32(1.0)
        if active is None
        else (jnp.asarray(active) != 0).astype(jnp.float32)
    )
    return jnp.stack(
        [
            jnp.asarray(lr, jnp.float32),
            act,
            jnp.asarray(mhat_scale, jnp.float32),
            jnp.asarray(vhat_scale, jnp.float32),
        ]
    ).reshape(1, _mu.SCAL_WIDTH)


def _aligned_leaves(tree, treedef, n):
    """Leaves of an optional companion tree, aligned with the params' leaves."""
    return [None] * n if tree is None else treedef.flatten_up_to(tree)


@functools.partial(
    jax.jit, static_argnames=("qmax", "topk_ratio", "use_thresh", "use_kernel")
)
def fake_compress(
    delta, residual=None, mask=None,
    *, qmax: int = 0, topk_ratio: float = 1.0, use_thresh: bool = False,
    use_kernel=None,
):
    """Simulated compressed-upload channel over a pytree, with error feedback.

    Per leaf: ``x = delta + residual`` (what the client would like to send),
    ``y = dequant(quant(x))`` (what the server reconstructs — this is the
    value that must enter the merge), ``new_residual = x - y`` (the un-sent
    remainder, carried into the next upload). Returns
    ``(y_tree, new_residual_tree)``.

    ``qmax`` of 127/7 selects int8/int4 fake-quantization with one scale per
    consecutive 128 values of the flattened leaf (the kernel's 128-lane row);
    ``use_thresh`` adds per-leaf top-k thresholding with ``k = max(1,
    ceil(topk_ratio · active))`` where ``active`` counts the leaf's nonzero
    ``mask`` entries (the leaf size when ``mask`` is None). The threshold and
    the top-k per-leaf scale need a global sort/reduce, so they are computed
    out here and ride into the kernel via the SMEM scalar row. ``residual``
    None means no error feedback (the returned residual is still valid).
    Leaves below one tile (or ``use_kernel=False``) take the oracle on the
    same tiled layout — row-wise scale grain is layout-significant.
    """
    per_leaf_scale = use_thresh and qmax > 0
    leaves_d, treedef = jax.tree.flatten(delta)
    leaves_r = _aligned_leaves(residual, treedef, len(leaves_d))
    leaves_mk = _aligned_leaves(mask, treedef, len(leaves_d))

    def one(d, r, mk):
        x = d if r is None else d + r.astype(d.dtype)
        thresh = jnp.float32(0.0)
        scale = jnp.float32(0.0)
        if use_thresh:
            flat = jnp.abs(x.reshape(-1)).astype(jnp.float32)
            n = flat.shape[0]
            if mk is None:
                active = jnp.float32(n)
            else:
                # mask leaves may be broadcastable (e.g. the (L, 1, 1) GAL
                # masks): each nonzero mask entry covers size//mk.size values
                active = jnp.sum((mk != 0).astype(jnp.float32)) * (
                    d.size // mk.size
                )
            k = jnp.maximum(1.0, jnp.ceil(topk_ratio * active)).astype(jnp.int32)
            thresh = jnp.sort(flat)[jnp.clip(n - k, 0, n - 1)]
            if qmax:
                scale = jnp.max(flat) / qmax
        x2 = _tile2d(x)
        zero = jnp.float32(0.0)
        scal = jnp.stack([thresh, scale, zero, zero]).reshape(1, _mu.SCAL_WIDTH)
        if _use_kernel(x.size, use_kernel):
            y2, r2 = _cp.fake_compress_2d(
                x2, scal, qmax=qmax, use_thresh=use_thresh,
                per_leaf_scale=per_leaf_scale,
            )
        else:
            y2, r2 = _ref.fake_compress_ref(
                x2, thresh, scale, qmax=qmax, use_thresh=use_thresh,
                per_leaf_scale=per_leaf_scale,
            )
        return _untile(y2, d), _untile(r2, d)

    outs = [one(*leaf) for leaf in zip(leaves_d, leaves_r, leaves_mk)]
    return (
        jax.tree.unflatten(treedef, [o[0] for o in outs]),
        jax.tree.unflatten(treedef, [o[1] for o in outs]),
    )


@functools.partial(jax.jit, static_argnames=("momentum", "use_kernel"))
def masked_sgd_update(
    grads, state, params, lr, mask=None, active=None,
    *, momentum: float = 0.0, use_kernel=None,
):
    """Fused masked SGD(+momentum) over a pytree — one kernel pass per leaf.

    Drop-in for :func:`repro.optim.optimizers.sgd_update` (same signature and
    frozen-moment semantics): entries with ``mask == 0`` — and every entry
    when ``active == 0`` (a padded curriculum step) — keep their parameter
    AND momentum bit-for-bit. Leaves below one tile (or with
    ``use_kernel=False``) take the equivalent single-expression oracle.
    """
    scal = _scal_row(lr, active)
    leaves_p, treedef = jax.tree.flatten(params)
    leaves_g = treedef.flatten_up_to(grads)
    leaves_mu = _aligned_leaves(state["mu"] if momentum else None, treedef, len(leaves_p))
    leaves_mk = _aligned_leaves(mask, treedef, len(leaves_p))

    def one(p, g, mu, mk):
        if not _use_kernel(p.size, use_kernel):
            return _ref.masked_sgd_update_ref(
                p, g, mu, mk, lr, momentum=momentum, active=active
            )
        new_p2, new_mu2 = _mu.masked_sgd_update_2d(
            _tile2d(p),
            _tile2d(g),
            _tile2d(mu) if momentum else None,
            _tile2d(mk) if mk is not None else None,
            scal,
            momentum=momentum,
        )
        return _untile(new_p2, p), (_untile(new_mu2, mu) if momentum else None)

    outs = [one(*leaf) for leaf in zip(leaves_p, leaves_g, leaves_mu, leaves_mk)]
    new_params = jax.tree.unflatten(treedef, [o[0] for o in outs])
    if momentum:
        return new_params, {"mu": jax.tree.unflatten(treedef, [o[1] for o in outs])}
    return new_params, state


@functools.partial(
    jax.jit, static_argnames=("b1", "b2", "eps", "wd", "use_kernel")
)
def masked_adamw_update(
    grads, state, params, lr, mask=None, active=None,
    *, b1=0.9, b2=0.999, eps=1e-8, wd=0.0, use_kernel=None,
):
    """Fused masked AdamW over a pytree — one kernel pass per leaf.

    Drop-in for :func:`repro.optim.optimizers.adamw_update`: frozen entries
    hold parameter, ``m``, and ``v`` bit-for-bit, and the step counter ``t``
    only advances on active steps, so a masked/padded step is a true no-op.
    Bias-correction scales are computed from ``t`` once out here and shared
    by every leaf's kernel call.
    """
    inc = (
        jnp.int32(1)
        if active is None
        else (jnp.asarray(active) != 0).astype(jnp.int32)
    )
    t = state["t"] + inc
    tf = t.astype(jnp.float32)
    mhat_scale = 1.0 / (1.0 - b1**tf)
    vhat_scale = 1.0 / (1.0 - b2**tf)
    scal = _scal_row(lr, active, mhat_scale, vhat_scale)
    leaves_p, treedef = jax.tree.flatten(params)
    leaves_g = treedef.flatten_up_to(grads)
    leaves_m = treedef.flatten_up_to(state["m"])
    leaves_v = treedef.flatten_up_to(state["v"])
    leaves_mk = _aligned_leaves(mask, treedef, len(leaves_p))

    def one(p, g, m, v, mk):
        if not _use_kernel(p.size, use_kernel):
            return _ref.masked_adamw_update_ref(
                p, g, m, v, mk, lr, mhat_scale, vhat_scale,
                b1=b1, b2=b2, eps=eps, wd=wd, active=active,
            )
        new_p2, new_m2, new_v2 = _mu.masked_adamw_update_2d(
            _tile2d(p),
            _tile2d(g),
            _tile2d(m),
            _tile2d(v),
            _tile2d(mk) if mk is not None else None,
            scal,
            b1=b1, b2=b2, eps=eps, wd=wd,
        )
        return _untile(new_p2, p), _untile(new_m2, m), _untile(new_v2, v)

    outs = [
        one(*leaf)
        for leaf in zip(leaves_p, leaves_g, leaves_m, leaves_v, leaves_mk)
    ]
    return jax.tree.unflatten(treedef, [o[0] for o in outs]), {
        "m": jax.tree.unflatten(treedef, [o[1] for o in outs]),
        "v": jax.tree.unflatten(treedef, [o[2] for o in outs]),
        "t": t,
    }
