"""Public fused-Gram ops: padding, block-size policy, precision casting /
int8 tile quantization.  Compiled on a TPU, interpreted on the CPU
(``repro.kernels.backend`` decides).

``gram``        — unbatched (N, L) entry point; a thin wrapper that runs the
                  agent-batched triangular kernel with a singleton agent axis
                  (``variant="dense"`` selects the dense-tile baseline kernel,
                  kept for benchmarking and padding-policy parity tests).
``gram_batched``— (m, N, L) entry point: sufficient statistics for ALL m
                  agents in ONE triangular-grid kernel launch.  The launch
                  emits the FULL symmetric G — the (j, i) tiles are written
                  in-kernel on a trailing mirror grid step, so there is no
                  VPU mirror round-trip here anymore.
``gram_fused``  — the fused feature->Gram producer: takes raw inputs
                  (X, W, b, T) and computes the ELM hidden layer
                  ``H = act(X W + b)`` inside the kernel, so H never hits
                  HBM at full precision.  ``force_ref`` (or off-TPU parity
                  tests) fall back to the materialized jnp oracle
                  (``ref.gram_fused_ref``) — bitwise-identical in fp32.

Block policy (shared, asserted): ``block_n`` is clamped to the padded sample
count and rounded up to a multiple of 8 (TPU fp32 sublane), so the padded N
is always an exact multiple of an aligned block — tiny or ragged streams
(N in {1, 7, 9, ...}) pad up instead of producing unaligned tiles.  Padding
is exact: zero rows/cols contribute nothing to either product (the fused
kernel enforces this with in-kernel masks, since act(0) != 0).

Precision (``precision="fp32" | "bf16" | "int8"``):

* bf16 casts H and T once at the op boundary and streams the halved-traffic
  tiles straight to the MXU with fp32 accumulators.  Expected error: bf16
  has an 8-bit mantissa, so G/R entries carry a relative error of order
  2^-8 ~ 4e-3 of the accumulated magnitude; documented test tolerance is
  3e-2 relative.
* int8 (triangular variant only — the recorded int8 study) quantizes H per
  (block_n, block_l) tile with a symmetric maxabs/127 scale and STOCHASTIC
  rounding (``floor(x/scale + u)``, u ~ U[0,1) — unbiased: E[q*scale] = x),
  then streams 1-byte tiles into the int8 MXU path with exact int32 tile
  accumulation (``kernel.gram_pallas_tri_q``); T streams in bf16.  The
  quantization pass itself runs at the op boundary in jnp (this jax build
  has no ``pltpu.stochastic_round``; on hardware that does, the same
  rounding can move in-kernel).  ``quant_seed`` (a traced int) selects the
  rounding stream, so averaging over seeds converges to the fp32 truth
  (asserted in tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.gram.kernel import (
    gram_pallas,
    gram_pallas_fused,
    gram_pallas_tri,
    gram_pallas_tri_q,
)
from repro.kernels.gram.ref import gram_fused_ref, gram_ref, int8_emulated_ref

PRECISIONS = ("fp32", "bf16", "int8")
FUSED_PRECISIONS = ("fp32", "bf16")


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def resolve_block_n(N: int, block_n: int) -> int:
    """The block policy, asserted: clamp to the padded sample count, then
    round up to the fp32 sublane multiple of 8.  The returned block always
    divides the padded N exactly (padding pads *to* a block multiple)."""
    block_n = max(8, min(block_n, _round_up(N, 8)))
    block_n = _round_up(block_n, 8)
    pad_n = (-N) % block_n
    if block_n % 8 != 0 or (N + pad_n) % block_n != 0:
        raise AssertionError(
            f"gram block policy violated: N={N}, block_n={block_n}, "
            f"padded N={N + pad_n} — block must be sublane-aligned and "
            f"divide the padded sample count"
        )
    return block_n


def _cast(H: jax.Array, T: jax.Array, precision: str):
    if precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; expected one of {PRECISIONS}"
        )
    if precision == "bf16":
        return H.astype(jnp.bfloat16), T.astype(jnp.bfloat16)
    return H.astype(jnp.float32), T.astype(jnp.float32)


def quantize_tiles(Hp: jax.Array, block_n: int, block_l: int,
                   quant_seed) -> tuple[jax.Array, jax.Array]:
    """Per-tile symmetric int8 quantization with stochastic rounding.

    Hp: (m, Np, Lp) fp32 with Np % block_n == 0, Lp % block_l == 0 (the
    kernel's padded layout).  Each (block_n, block_l) tile gets one fp32
    scale ``maxabs/127``; entries quantize as ``floor(x/scale + u)`` with
    u ~ U[0, 1), which is UNBIASED (E[q] = x/scale exactly, including at
    the +-127 extremes) — the mean over ``quant_seed`` draws converges to
    the fp32 value.  Zero entries (padding rows/cols) quantize to exactly
    0 for every u < 1, so padding stays exact.

    Returns (Hq (m, Np, Lp) int8, scales (m, Np/block_n, Lp/block_l) fp32).
    """
    m, Np, Lp = Hp.shape
    nn, nl = Np // block_n, Lp // block_l
    tiles = Hp.astype(jnp.float32).reshape(m, nn, block_n, nl, block_l)
    amax = jnp.max(jnp.abs(tiles), axis=(2, 4))            # (m, nn, nl)
    scales = jnp.maximum(amax, jnp.float32(1e-30)) / 127.0
    x = tiles / scales[:, :, None, :, None]
    u = jax.random.uniform(
        jax.random.PRNGKey(jnp.asarray(quant_seed, jnp.uint32)), tiles.shape
    )
    q = jnp.clip(jnp.floor(x + u), -127, 127).astype(jnp.int8)
    return q.reshape(m, Np, Lp), scales


def quantize_dequantize(H: jax.Array, *, block_l: int = 128,
                        block_n: int = 512, quant_seed=0) -> jax.Array:
    """The int8 emulation used by the oracle path and the unbiasedness
    tests: pad H exactly as the kernel would, quantize per tile, and
    dequantize back to fp32 (unpadded).  H: (m, N, L)."""
    m, N, L = H.shape
    block_n = resolve_block_n(N, block_n)
    pad_n = (-N) % block_n
    pad_l = (-L) % block_l
    Hp = jnp.pad(H.astype(jnp.float32), ((0, 0), (0, pad_n), (0, pad_l)))
    q, scales = quantize_tiles(Hp, block_n, block_l, quant_seed)
    nn, nl = Hp.shape[1] // block_n, Hp.shape[2] // block_l
    deq = (
        q.reshape(m, nn, block_n, nl, block_l).astype(jnp.float32)
        * scales[:, :, None, :, None]
    ).reshape(Hp.shape)
    return deq[:, :N, :L]


@functools.partial(
    jax.jit,
    static_argnames=("block_l", "block_n", "force_ref", "variant",
                     "precision"),
)
def gram(H: jax.Array, T: jax.Array, *, block_l: int = 128,
         block_n: int = 512, force_ref: bool = False,
         variant: str = "tri", precision: str = "fp32",
         quant_seed=0):
    """Fused (H^T H, H^T T) for one agent. H: (N, L), T: (N, D).

    ``variant="tri"`` (default) runs the symmetry-aware triangular kernel
    through the batched launcher with a singleton agent axis;
    ``variant="dense"`` runs the all-tiles baseline.  Both share the padding
    and precision policy, so they are interchangeable bit-for-bit in fp32
    up to tile-reduction order.  ``precision="int8"`` is triangular-only.
    """
    if precision == "int8":
        if variant != "tri":
            raise ValueError(
                "precision='int8' requires variant='tri' (the dense "
                "baseline has no int8 path)"
            )
        G, R = gram_batched(H[None], T[None], block_l=block_l,
                            block_n=block_n, force_ref=force_ref,
                            precision=precision, quant_seed=quant_seed)
        return G[0], R[0]
    if force_ref:
        H, T = _cast(H, T, precision)   # bf16 rounding applies to the
        return gram_ref(H, T)           # oracle path too, not just tiles
    if variant == "tri":
        G, R = gram_batched(H[None], T[None], block_l=block_l,
                            block_n=block_n, precision=precision)
        return G[0], R[0]
    if variant != "dense":
        raise ValueError(f"unknown variant {variant!r}; 'tri' or 'dense'")
    N, L = H.shape
    block_n = resolve_block_n(N, block_n)
    pad_n = (-N) % block_n
    pad_l = (-L) % block_l
    H, T = _cast(H, T, precision)
    Hp = jnp.pad(H, ((0, pad_n), (0, pad_l)))
    Tp = jnp.pad(T, ((0, pad_n), (0, 0)))
    G, R = gram_pallas(
        Hp, Tp, block_l=block_l, block_n=block_n,
        interpret=backend.interpret(),
    )
    return G[:L, :L], R[:L]


@functools.partial(
    jax.jit, static_argnames=("block_l", "block_n", "force_ref", "precision")
)
def gram_batched(H: jax.Array, T: jax.Array, *, block_l: int = 128,
                 block_n: int = 512, force_ref: bool = False,
                 precision: str = "fp32", quant_seed=0):
    """Per-agent (H^T H, H^T T) for ALL m agents in ONE kernel launch.

    H: (m, N, L), T: (m, N, D).  Returns (G (m, L, L), R (m, L, D)), both
    fp32.  The launch grid is ``(m, tri, n + 1)`` — the agent axis is the
    outermost grid dimension of a single pipelined Pallas program, not an
    m-fold vmap of separate launches, and the trailing mirror step per tile
    pair writes the full symmetric G in-kernel.

    ``precision="int8"`` streams per-tile-quantized 1-byte H tiles
    (stochastic rounding seeded by ``quant_seed``) and bf16 T tiles; the
    ``force_ref`` oracle reproduces the same quantization in jnp.
    """
    if precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; expected one of {PRECISIONS}"
        )
    m, N, L = H.shape
    if precision == "int8":
        resolved_bn = resolve_block_n(N, block_n)
        if force_ref:
            Hdq = quantize_dequantize(H, block_l=block_l,
                                      block_n=resolved_bn,
                                      quant_seed=quant_seed)
            return jax.vmap(int8_emulated_ref)(Hdq, T)
        pad_n = (-N) % resolved_bn
        pad_l = (-L) % block_l
        Hp = jnp.pad(H.astype(jnp.float32),
                     ((0, 0), (0, pad_n), (0, pad_l)))
        Tp = jnp.pad(T.astype(jnp.bfloat16), ((0, 0), (0, pad_n), (0, 0)))
        Hq, scales = quantize_tiles(Hp, resolved_bn, block_l, quant_seed)
        G, R = gram_pallas_tri_q(
            Hq, scales, Tp, block_l=block_l, block_n=resolved_bn,
            interpret=backend.interpret(),
        )
        return G[:, :L, :L], R[:, :L]
    if force_ref:
        H, T = _cast(H, T, precision)
        return jax.vmap(gram_ref)(H, T)
    block_n = resolve_block_n(N, block_n)
    pad_n = (-N) % block_n
    pad_l = (-L) % block_l
    H, T = _cast(H, T, precision)
    Hp = jnp.pad(H, ((0, 0), (0, pad_n), (0, pad_l)))
    Tp = jnp.pad(T, ((0, 0), (0, pad_n), (0, 0)))
    G, R = gram_pallas_tri(
        Hp, Tp, block_l=block_l, block_n=block_n,
        interpret=backend.interpret(),
    )
    return G[:, :L, :L], R[:, :L]


@functools.partial(
    jax.jit,
    static_argnames=("activation", "block_l", "block_n", "force_ref",
                     "precision"),
)
def gram_fused(X: jax.Array, W: jax.Array, b: jax.Array, T: jax.Array, *,
               activation: str = "sigmoid", block_l: int = 128,
               block_n: int = 512, force_ref: bool = False,
               precision: str = "fp32"):
    """The fused feature->Gram producer: sufficient statistics straight
    from raw inputs, hidden layer computed IN-KERNEL.

    X: (m, N, d_in) or (N, d_in) raw (backbone) features; W: (d_in, L),
    b: (L,) — the frozen ELM hidden layer ``H = act(X W + b)``; T matches
    X's leading shape with trailing D.  Returns (G, R) exactly like
    ``gram_batched`` on the materialized H — bitwise-identical in fp32 to
    an H built from the same (block_n, block_l) tile products (asserted in
    tests), because the kernel applies the same activation to the same
    unpadded-d_in contraction and masks padding to exact zero.

    ``precision="bf16"`` rounds the hidden tiles (and T) to bf16 before the
    MXU contraction, matching the materialized bf16 stream; int8 is not
    offered on the fused path (quantization scales need a tile maxabs pass,
    which would force H back through memory — use the unfused int8 stream).
    """
    if precision not in FUSED_PRECISIONS:
        raise ValueError(
            f"fused precision must be one of {FUSED_PRECISIONS}, got "
            f"{precision!r} (int8 needs a materialized maxabs pass — use "
            f"gram_batched(precision='int8'))"
        )
    batched = X.ndim == 3
    if not batched:
        X, T = X[None], T[None]
    m, N, d_in = X.shape
    L = W.shape[1]
    if force_ref:
        G, R = jax.vmap(
            lambda x, t: gram_fused_ref(x, W, b, t, activation=activation,
                                        precision=precision)
        )(X, T)
        return (G, R) if batched else (G[0], R[0])
    block_n = resolve_block_n(N, block_n)
    pad_n = (-N) % block_n
    pad_l = (-L) % block_l
    t_dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32
    Xp = jnp.pad(X.astype(jnp.float32), ((0, 0), (0, pad_n), (0, 0)))
    Wp = jnp.pad(W.astype(jnp.float32), ((0, 0), (0, pad_l)))
    bp = jnp.pad(b.astype(jnp.float32), (0, pad_l)).reshape(1, -1)
    Tp = jnp.pad(T.astype(t_dtype), ((0, 0), (0, pad_n), (0, 0)))
    compute_dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32
    G, R = gram_pallas_fused(
        Xp, Wp, bp, Tp, n_true=N, l_true=L, activation=activation,
        block_l=block_l, block_n=block_n, compute_dtype=compute_dtype,
        interpret=backend.interpret(),
    )
    G, R = G[:, :L, :L], R[:, :L]
    return (G, R) if batched else (G[0], R[0])
