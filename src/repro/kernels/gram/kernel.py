"""Fused Gram accumulation kernels: G = H^T H and R = H^T T in ONE pass
over the sample dimension N.

This is the FLOPs hot-spot of the paper's algorithm family (every ELM /
MTL-ELM / DMTL-ELM solve starts from these statistics; at backbone scale
L = d_model it dominates the head fit).  Streaming H through VMEM once
instead of twice halves HBM traffic versus two separate matmuls — and G is
*symmetric*, so visiting every (i, j) tile pair wastes close to half the MXU
work on mirrored tiles.  Two kernels:

``gram_pallas`` — the dense-tile baseline (kept for benchmarking and as the
    simplest correct tiling).  Grid ``(i, j, n)`` over
    ``(L/BL, L/BL, N/BN)``; the last axis iterates sequentially on TPU, so
    the fp32 accumulators live in the output VMEM tiles across n-steps.
    R is accumulated by the ``j == 0`` column of the grid only.

``gram_pallas_tri`` — the symmetry-aware, agent-batched production kernel.
    The (i, j) tile plane is flattened to a single triangular grid axis that
    enumerates only the lower-triangular block pairs ``(i, j <= i)`` in
    row-major order (``t = i(i+1)/2 + j``), cutting G's MXU tile-matmuls
    from ``nl^2`` to ``nl(nl+1)/2`` — a ``2 nl / (nl + 1)``-fold FLOPs
    reduction that approaches 2x as the block grid refines.  A leading
    agent axis batches all ``m`` agents' statistics into ONE kernel launch
    instead of ``m`` vmapped launches, so the whole multi-task stats pass
    is a single pipelined Pallas program.  The grid is ``(m, tri, n + 1)``:
    each pair's accumulator lives in a VMEM scratch tile across the n
    sample steps, the last sample step flushes it to the (i, j) output
    block, and one extra MIRROR grid step per pair writes the transpose to
    the (j, i) block — the full symmetric G leaves the kernel in a single
    launch, with no VPU mirror round-trip outside it.

``gram_pallas_tri_q`` — the int8-streaming twin of the triangular kernel
    (the recorded int8 study): H tiles arrive as int8 with one fp32 scale
    per (n, l) tile (quantized with stochastic rounding at the op
    boundary — see ``ops.quantize_tiles``), the tile product runs on the
    int8 MXU path into an exact int32 accumulator, and each n-step's
    contribution is scaled by ``scale_i * scale_j`` into the fp32 VMEM
    accumulator.  R dequantizes the row tile (VPU scale) and contracts
    against bf16 T tiles in fp32.

``gram_pallas_fused`` — the fused feature->Gram producer: the grid streams
    (BN, d_in) X tiles and computes the ELM hidden layer
    ``h = act(X W + b)`` INSIDE the kernel (two (d_in, BL) W column tiles
    per pair), so H never exists in HBM at full precision — the O(N L)
    materialize write + re-read of the unfused pipeline disappears
    entirely.  Padded rows/columns are masked to exact zero in-kernel
    (``act(0) != 0`` for sigmoid-family activations, so zero-padding the
    inputs alone would corrupt the statistics); ``d_in`` is NOT padded, so
    each hidden tile is the same (BN, d_in) x (d_in, BL) product as the
    materialized ``act(X @ W + b)`` built from those tiles, and the fused
    fp32 producer matches the materialized kernel on it bit for bit
    (asserted in tests).

Mixed precision: the kernels stream H / T tiles in their *input* dtype and
hand them straight to the MXU with fp32 accumulation (``_dot``): bf16
inputs take the native bf16-multiply / fp32-accumulate path (half the HBM
read traffic), fp32 inputs contract at HIGHEST precision (the TPU's
default would round them to bf16), and the G / R accumulators stay fp32;
int8 quarters the read traffic again through ``gram_pallas_tri_q``.
``ops.gram(..., precision=...)`` does the downcast / quantization at the op
boundary.

Tiling: MXU-aligned BL=128 by default; BN chosen so the (BN, BL) H tiles
and the (BL, BL) accumulator fit VMEM comfortably (3 * 128*512*4B ~ 0.8 MB).
The triangular index decode runs in the scalar index maps (exact integer
arithmetic seeded by a float sqrt, corrected by +-1 where-steps).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Kept in lockstep with ``repro.core.elm.ACTIVATIONS`` (asserted in tests):
# the fused kernel must apply the exact same activation callables as the
# materialized ``ELMFeatureMap`` path for the bitwise-parity oracle to hold.
# Duplicated here rather than imported so the kernel package stays free of
# ``repro.core`` dependencies.
ACTIVATIONS = {
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
}


def tri_count(nl: int) -> int:
    """Number of lower-triangular (i, j <= i) block pairs of an nl x nl grid."""
    return nl * (nl + 1) // 2


def _tri_decode(t):
    """Row-major lower-triangular decode: t = i(i+1)/2 + j  ->  (i, j).

    Exact for any t reachable here (tri grids are tiny): the float sqrt
    seeds the row index and two where-corrections pin it to the integer
    triangle-number bracket ``i(i+1)/2 <= t < (i+1)(i+2)/2``.
    """
    t = jnp.asarray(t, jnp.int32)
    i = ((jnp.sqrt(8.0 * t.astype(jnp.float32) + 1.0) - 1.0) * 0.5).astype(
        jnp.int32
    )
    i = jnp.where(i * (i + 1) // 2 > t, i - 1, i)
    i = jnp.where((i + 1) * (i + 2) // 2 <= t, i + 1, i)
    return i, t - i * (i + 1) // 2


def _dot(a, b, contract):
    """MXU contraction into an fp32 accumulator.  fp32 operands contract at
    HIGHEST: the TPU's DEFAULT precision for them is one bf16 pass, which
    on a v5e gave fp32 statistics the error of bf16 ones (2e-4 relative
    on G against a HIGHEST einsum, chip_smoke.py).  bf16 operands are
    exact in one pass."""
    precision = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), precision=precision,
        preferred_element_type=jnp.float32,
    )


def _gram_kernel(h_i_ref, h_j_ref, t_ref, g_ref, r_ref, *, n_steps):
    n = pl.program_id(2)
    j = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    hi = h_i_ref[...]   # (BN, BL) rows n, cols i — input dtype (f32 or bf16)
    hj = h_j_ref[...]   # (BN, BL) rows n, cols j
    g_ref[...] += _dot(hi, hj, ((0,), (0,)))

    @pl.when(j == 0)
    def _cross():
        @pl.when(n == 0)
        def _init_r():
            r_ref[...] = jnp.zeros_like(r_ref)

        t = t_ref[...]  # (BN, D)
        r_ref[...] += _dot(hi, t, ((0,), (0,)))


def gram_pallas(H: jax.Array, T: jax.Array, *, block_l: int = 128,
                block_n: int = 512, interpret: bool = False):
    """Dense-tile baseline. H: (N, L), T: (N, D); N % block_n == 0,
    L % block_l == 0 (pre-padded by ops.gram). Returns (G (L,L) fp32,
    R (L,D) fp32); inputs stream in their own dtype (fp32 or bf16)."""
    N, L = H.shape
    D = T.shape[1]
    nl = L // block_l
    nn = N // block_n
    grid = (nl, nl, nn)

    # T is only read on the j == 0 (R-accumulating) grid column; pinning its
    # block index on every other step stops the pipeline refetching a tile
    # the kernel never touches — T traffic is nl*nn fetches, not nl^2*nn.
    def t_spec(i, j, n):
        return (jnp.where(j == 0, n, 0), 0)

    return pl.pallas_call(
        functools.partial(_gram_kernel, n_steps=nn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_l), lambda i, j, n: (n, i)),
            pl.BlockSpec((block_n, block_l), lambda i, j, n: (n, j)),
            pl.BlockSpec((block_n, D), t_spec),
        ],
        out_specs=[
            pl.BlockSpec((block_l, block_l), lambda i, j, n: (i, j)),
            pl.BlockSpec((block_l, D), lambda i, j, n: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, L), jnp.float32),
            jax.ShapeDtypeStruct((L, D), jnp.float32),
        ],
        interpret=interpret,
    )(H, H, T)


def _gram_tri_kernel(h_i_ref, h_j_ref, t_ref, g_ref, r_ref, acc_ref, *,
                     n_steps):
    n = pl.program_id(2)
    _, j = _tri_decode(pl.program_id(1))

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n < n_steps)
    def _accumulate():
        hi = h_i_ref[0]  # (BN, BL) rows n, cols i — input dtype (f32/bf16)
        hj = h_j_ref[0]  # (BN, BL) rows n, cols j <= i
        acc_ref[...] += _dot(hi, hj, ((0,), (0,)))

        # R rides the diagonal-start column of each row: the j == 0 pair is
        # the FIRST tri index of row i, so the (a, i, 0) R tile initializes
        # and accumulates before any other pair of that row revisits it.
        @pl.when(j == 0)
        def _cross():
            @pl.when(n == 0)
            def _init_r():
                r_ref[...] = jnp.zeros_like(r_ref)

            t = t_ref[0]    # (BN, D)
            r_ref[0] += _dot(hi, t, ((0,), (0,)))

    # Flush the finished accumulator to the (i, j) tile on the LAST sample
    # step, then write its transpose to the (j, i) tile on the extra mirror
    # step — the output BlockSpec swaps the tile coordinates exactly when
    # n == n_steps, so both writes land inside one launch and the full
    # symmetric G leaves the kernel.  Diagonal pairs (i == j) overwrite
    # their tile with its own transpose: a bitwise no-op, since the tile
    # dot h_i^T h_i is exactly symmetric.
    @pl.when(n == n_steps - 1)
    def _flush():
        g_ref[0] = acc_ref[...]

    @pl.when(n == n_steps)
    def _mirror():
        g_ref[0] = acc_ref[...].T


def gram_pallas_tri(H: jax.Array, T: jax.Array, *, block_l: int = 128,
                    block_n: int = 512, interpret: bool = False):
    """Symmetry-aware agent-batched kernel: ONE launch for all m agents.

    H: (m, N, L), T: (m, N, D); N % block_n == 0, L % block_l == 0
    (pre-padded by ops).  Grid ``(m, tri, n + 1)`` visits only the
    ``nl(nl+1)/2`` lower-triangular (i, j <= i) block pairs per agent; the
    extra trailing grid step per pair mirrors the accumulated tile into the
    (j, i) position (input index maps are pinned there, so nothing is
    refetched).

    Returns (G (m, L, L) fp32, R (m, L, D) fp32) with G FULLY written —
    both triangles come out of the single launch, exactly symmetric at
    block granularity.
    """
    m, N, L = H.shape
    D = T.shape[-1]
    nl = L // block_l
    nn = N // block_n
    grid = (m, tri_count(nl), nn + 1)

    # Mirror step (n == nn) reads nothing: pin every input index map to the
    # previous step's block so the pipeline does not refetch a tile the
    # kernel never touches.
    def h_row_spec(a, t, n):
        i, _ = _tri_decode(t)
        return (a, jnp.minimum(n, nn - 1), i)

    def h_col_spec(a, t, n):
        _, j = _tri_decode(t)
        return (a, jnp.minimum(n, nn - 1), j)

    def g_spec(a, t, n):
        i, j = _tri_decode(t)
        mirror = n == nn
        return (a, jnp.where(mirror, j, i), jnp.where(mirror, i, j))

    # see gram_pallas: T is only read on j == 0 sample steps, so pin the
    # block index elsewhere and the pipeline fetches T nl*nn times
    def t_spec(a, t, n):
        _, j = _tri_decode(t)
        return (a, jnp.where(j == 0, jnp.minimum(n, nn - 1), 0), 0)

    return pl.pallas_call(
        functools.partial(_gram_tri_kernel, n_steps=nn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n, block_l), h_row_spec),
            pl.BlockSpec((1, block_n, block_l), h_col_spec),
            pl.BlockSpec((1, block_n, D), t_spec),
        ],
        out_specs=[
            pl.BlockSpec((1, block_l, block_l), g_spec),
            pl.BlockSpec((1, block_l, D), lambda a, t, n: (a, _tri_decode(t)[0], 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, L, L), jnp.float32),
            jax.ShapeDtypeStruct((m, L, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_l, block_l), jnp.float32)],
        interpret=interpret,
    )(H, H, T)


def _gram_tri_q_kernel(h_i_ref, h_j_ref, s_ref, t_ref, g_ref, r_ref,
                       acc_ref, *, n_steps):
    n = pl.program_id(2)
    i, j = _tri_decode(pl.program_id(1))

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n < n_steps)
    def _accumulate():
        hi = h_i_ref[0]                 # (BN, BL) int8
        hj = h_j_ref[0]                 # (BN, BL) int8
        s_i = s_ref[0, n, i]            # fp32 scale of the (n, i) tile
        s_j = s_ref[0, n, j]            # fp32 scale of the (n, j) tile
        # int8 x int8 -> exact int32 tile product on the MXU int path; each
        # n-step's contribution is scaled into the fp32 accumulator (scales
        # vary per n tile, so the int32 sums cannot be merged across n).
        prod = jax.lax.dot_general(
            hi, hj, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        acc_ref[...] += prod.astype(jnp.float32) * (s_i * s_j)

        @pl.when(j == 0)
        def _cross():
            @pl.when(n == 0)
            def _init_r():
                r_ref[...] = jnp.zeros_like(r_ref)

            hi_dq = hi.astype(jnp.float32) * s_i    # VPU dequantize
            t = t_ref[0].astype(jnp.float32)        # (BN, D), bf16 stream
            r_ref[0] += _dot(hi_dq, t, ((0,), (0,)))

    @pl.when(n == n_steps - 1)
    def _flush():
        g_ref[0] = acc_ref[...]

    @pl.when(n == n_steps)
    def _mirror():
        g_ref[0] = acc_ref[...].T


def gram_pallas_tri_q(Hq: jax.Array, scales: jax.Array, T: jax.Array, *,
                      block_l: int = 128, block_n: int = 512,
                      interpret: bool = False):
    """int8-streaming triangular kernel (the recorded int8 study).

    Hq: (m, N, L) int8 — quantized per (block_n, block_l) tile with
    ``scales``: (m, N/block_n, L/block_l) fp32 (see ``ops.quantize_tiles``),
    each agent's table held in SMEM and read as scalars;
    T: (m, N, D) bf16.  Same grid / mirror structure as
    :func:`gram_pallas_tri`; H read traffic is 1 byte/element (4x less than
    fp32, 2x less than bf16) while G/R stay fp32.
    """
    m, N, L = Hq.shape
    D = T.shape[-1]
    nl = L // block_l
    nn = N // block_n
    grid = (m, tri_count(nl), nn + 1)

    def h_row_spec(a, t, n):
        i, _ = _tri_decode(t)
        return (a, jnp.minimum(n, nn - 1), i)

    def h_col_spec(a, t, n):
        _, j = _tri_decode(t)
        return (a, jnp.minimum(n, nn - 1), j)

    def g_spec(a, t, n):
        i, j = _tri_decode(t)
        mirror = n == nn
        return (a, jnp.where(mirror, j, i), jnp.where(mirror, i, j))

    def t_spec(a, t, n):
        _, j = _tri_decode(t)
        return (a, jnp.where(j == 0, jnp.minimum(n, nn - 1), 0), 0)

    return pl.pallas_call(
        functools.partial(_gram_tri_q_kernel, n_steps=nn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n, block_l), h_row_spec),
            pl.BlockSpec((1, block_n, block_l), h_col_spec),
            # one agent's whole (nn, nl) scale table in SMEM, read as
            # scalars: a (1, 1, 1) VMEM block is not (8, 128)-aligned
            pl.BlockSpec((1, nn, nl), lambda a, t, n: (a, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_n, D), t_spec),
        ],
        out_specs=[
            pl.BlockSpec((1, block_l, block_l), g_spec),
            pl.BlockSpec((1, block_l, D), lambda a, t, n: (a, _tri_decode(t)[0], 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, L, L), jnp.float32),
            jax.ShapeDtypeStruct((m, L, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_l, block_l), jnp.float32)],
        interpret=interpret,
    )(Hq, Hq, scales, T)


def _gram_fused_kernel(x_ref, w_i_ref, w_j_ref, b_i_ref, b_j_ref, t_ref,
                       g_ref, r_ref, acc_ref, hi_ref, *, n_steps, n_true,
                       l_true, block_n, block_l, activation, compute_dtype):
    n = pl.program_id(2)
    i, j = _tri_decode(pl.program_id(1))
    act = ACTIVATIONS[activation]

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n < n_steps)
    def _accumulate():
        x = x_ref[0]                     # (BN, d_in) fp32, rows n
        # The hidden layer, computed in VMEM and never written to HBM.
        # Padded rows/cols MUST be masked to exact zero: act(0) != 0 for
        # sigmoid-family activations, so zero-padded inputs alone would
        # pollute the statistics.  d_in is not padded (ops), keeping the
        # per-tile contraction bitwise-identical to the materialized
        # act(X @ W + b).
        row_ok = (
            n * block_n
            + jax.lax.broadcasted_iota(jnp.int32, (block_n, 1), 0)
        ) < n_true

        def hidden(w_ref, b_ref, col):
            h = act(_dot(x, w_ref[...], ((1,), (0,))) + b_ref[...])
            col_ok = (
                col * block_l
                + jax.lax.broadcasted_iota(jnp.int32, (1, block_l), 1)
            ) < l_true
            h = jnp.where(row_ok & col_ok, h, 0.0)
            return h.astype(compute_dtype)

        hi = hidden(w_i_ref, b_i_ref, i)
        hj = hidden(w_j_ref, b_j_ref, j)
        acc_ref[...] += _dot(hi, hj, ((0,), (0,)))

        @pl.when(j == 0)
        def _cross():
            @pl.when(n == 0)
            def _init_r():
                r_ref[...] = jnp.zeros_like(r_ref)

            # R contracts the row tile a second time.  It goes through VMEM
            # scratch rather than reusing the value: the TPU compiler
            # refuses one computed value feeding two transposed-operand
            # matmuls (a RET_CHECK in its MXU transpose assignment).  Store
            # and load are exact, so the products are unchanged.
            hi_ref[...] = hi
            t = t_ref[0]    # (BN, D) — already masked-by-padding (zeros)
            r_ref[0] += _dot(hi_ref[...], t, ((0,), (0,)))

    @pl.when(n == n_steps - 1)
    def _flush():
        g_ref[0] = acc_ref[...]

    @pl.when(n == n_steps)
    def _mirror():
        g_ref[0] = acc_ref[...].T


def _fused_vmem_bytes(block_n: int, block_l: int, d_in: int) -> int:
    """Scoped-VMEM limit for the fused kernel: the v5e compiler's
    requirement plus a quarter.  The requirement is one term in ``d_in`` —
    the (block_n + 2 block_l) x d_in elements of the X tile and the two W
    panels, at 56/3 bytes each (double-buffered fp32 plus the bf16 parts of
    the HIGHEST contraction) — and under 1 MiB for the G/R tiles and
    scratch.  The compiler reported 28.6 MiB at d_in = 2048 and 56.6 MiB
    at 4096 with (512, 128) blocks, above the default scoped limit and
    under the 128 MiB of a v5e core."""
    need = (block_n + 2 * block_l) * d_in * 56 // 3 + (1 << 20)
    return need * 5 // 4


def gram_pallas_fused(X: jax.Array, W: jax.Array, b: jax.Array,
                      T: jax.Array, *, n_true: int, l_true: int,
                      activation: str = "sigmoid", block_l: int = 128,
                      block_n: int = 512, compute_dtype=jnp.float32,
                      interpret: bool = False):
    """Fused feature->Gram kernel: H = act(X W + b) never touches HBM.

    X: (m, N, d_in) fp32 (N padded to block_n; rows >= n_true are padding);
    W: (d_in, L) fp32, b: (1, L) fp32 (L padded to block_l; cols >= l_true
    are padding); T: (m, N, D) in the streaming dtype (fp32 or bf16,
    zero-padded).  ``compute_dtype`` is the dtype the hidden tiles are
    stored in before the MXU contraction (bf16 emulates the materialized
    bf16 stream).  Same (m, tri, n + 1) mirror grid as
    :func:`gram_pallas_tri`.  ``d_in`` is streamed unpadded — on real TPU
    hardware prefer sublane-aligned d_in (multiple of 8).
    """
    m, N, d_in = X.shape
    L = W.shape[1]
    D = T.shape[-1]
    nl = L // block_l
    nn = N // block_n
    grid = (m, tri_count(nl), nn + 1)

    def x_spec(a, t, n):
        return (a, jnp.minimum(n, nn - 1), 0)

    def w_row_spec(a, t, n):
        i, _ = _tri_decode(t)
        return (0, i)

    def w_col_spec(a, t, n):
        _, j = _tri_decode(t)
        return (0, j)

    def b_row_spec(a, t, n):
        i, _ = _tri_decode(t)
        return (0, i)

    def b_col_spec(a, t, n):
        _, j = _tri_decode(t)
        return (0, j)

    def g_spec(a, t, n):
        i, j = _tri_decode(t)
        mirror = n == nn
        return (a, jnp.where(mirror, j, i), jnp.where(mirror, i, j))

    def t_spec(a, t, n):
        _, j = _tri_decode(t)
        return (a, jnp.where(j == 0, jnp.minimum(n, nn - 1), 0), 0)

    return pl.pallas_call(
        functools.partial(
            _gram_fused_kernel, n_steps=nn, n_true=n_true, l_true=l_true,
            block_n=block_n, block_l=block_l, activation=activation,
            compute_dtype=compute_dtype,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n, d_in), x_spec),
            pl.BlockSpec((d_in, block_l), w_row_spec),
            pl.BlockSpec((d_in, block_l), w_col_spec),
            pl.BlockSpec((1, block_l), b_row_spec),
            pl.BlockSpec((1, block_l), b_col_spec),
            pl.BlockSpec((1, block_n, D), t_spec),
        ],
        out_specs=[
            pl.BlockSpec((1, block_l, block_l), g_spec),
            pl.BlockSpec((1, block_l, D), lambda a, t, n: (a, _tri_decode(t)[0], 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, L, L), jnp.float32),
            jax.ShapeDtypeStruct((m, L, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_l, block_l), jnp.float32),
            pltpu.VMEM((block_n, block_l), compute_dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_fused_vmem_bytes(block_n, block_l, d_in)),
        interpret=interpret,
    )(X, W, W, b, b, T)
