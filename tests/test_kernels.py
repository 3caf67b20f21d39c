"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed in interpret mode on CPU (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gram.ops import gram, gram_batched, resolve_block_n
from repro.kernels.gram.ref import gram_ref
from repro.kernels.rglru.ops import rglru_scan
from repro.kernels.rglru.ref import rglru_scan_ref
from repro.kernels.swa.ops import swa_attention
from repro.kernels.swa.ref import swa_ref

TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


# ----------------------------- gram ---------------------------------------

@pytest.mark.parametrize("variant", ["tri", "dense"])
@pytest.mark.parametrize("N,L,D", [(64, 32, 1), (100, 70, 3), (256, 128, 8),
                                   (33, 129, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_sweep(N, L, D, dtype, variant):
    k1, k2 = jax.random.split(jax.random.PRNGKey(N * L + D))
    H = jax.random.normal(k1, (N, L), dtype)
    T = jax.random.normal(k2, (N, D), dtype)
    G, R = gram(H, T, block_l=32, block_n=32, variant=variant)
    Gr, Rr = gram_ref(H, T)
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gr), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(R), np.asarray(Rr), **TOL[dtype])


@pytest.mark.parametrize("variant", ["tri", "dense"])
@pytest.mark.parametrize("N,L,D", [(5, 3, 1), (3, 129, 2), (7, 200, 1),
                                   (12, 70, 3), (1, 5, 1), (8, 70, 2),
                                   (9, 129, 1)])
def test_gram_odd_shapes_default_blocks(N, L, D, variant):
    """Default block policy on tiny/ragged N (1, 3, 5, 7, 8, 9, 12) and
    non-multiple-of-128 L, for BOTH tile layouts: the clamp must keep
    block_n sublane-aligned (multiple of 8) and pad exactly."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(N * 1000 + L))
    H = jax.random.normal(k1, (N, L))
    T = jax.random.normal(k2, (N, D))
    G, R = gram(H, T, variant=variant)   # default block_l=128, block_n=512
    Gr, Rr = gram_ref(H, T)
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gr), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(R), np.asarray(Rr), rtol=2e-4,
                               atol=2e-4)


def test_gram_block_policy_invariant():
    """resolve_block_n must always return a sublane-aligned block that
    divides the padded sample count exactly — including unaligned
    user-passed block sizes and tiny streams."""
    for N in (1, 5, 7, 8, 9, 12, 100, 513, 4096):
        for bn in (1, 7, 8, 12, 100, 512, 10_000):
            blk = resolve_block_n(N, bn)
            assert blk % 8 == 0
            padded = -(-N // blk) * blk
            assert padded % blk == 0
            assert blk <= padded
    # an unaligned block request still yields exact results
    H = jax.random.normal(jax.random.PRNGKey(0), (37, 40))
    T = jax.random.normal(jax.random.PRNGKey(1), (37, 2))
    Gr, Rr = gram_ref(H, T)
    for variant in ("tri", "dense"):
        G, R = gram(H, T, block_l=32, block_n=12, variant=variant)
        np.testing.assert_allclose(np.asarray(G), np.asarray(Gr), rtol=2e-4,
                                   atol=2e-4)


def test_gram_symmetry_and_psd():
    """The mirrored triangular output is EXACTLY symmetric (the upper
    triangle is the transpose of the written lower tiles by construction);
    the dense baseline is symmetric to float tolerance only."""
    H = jax.random.normal(jax.random.PRNGKey(0), (80, 40))
    G, _ = gram(H, jnp.zeros((80, 1)), block_l=32, block_n=16)
    np.testing.assert_array_equal(np.asarray(G), np.asarray(G).T)
    eig = np.linalg.eigvalsh(np.asarray(G))
    assert eig.min() > -1e-3
    Gd, _ = gram(H, jnp.zeros((80, 1)), block_l=32, block_n=16,
                 variant="dense")
    np.testing.assert_allclose(np.asarray(Gd), np.asarray(Gd).T, atol=1e-4)


def test_gram_tri_fp32_tight_tolerance():
    """Acceptance contract: the triangular agent-batched kernel matches
    gram_ref to <= 1e-5 max-abs in fp32 (O(1)-scaled statistics) across a
    padding edge case (L not a multiple of the block)."""
    m, N, L, D = 3, 100, 70, 2
    k1, k2 = jax.random.split(jax.random.PRNGKey(42))
    H = jax.random.normal(k1, (m, N, L)) / jnp.sqrt(N)
    T = jax.random.normal(k2, (m, N, D))
    G, R = gram_batched(H, T, block_l=32, block_n=32)
    Gr, Rr = jax.vmap(gram_ref)(H, T)
    assert float(jnp.max(jnp.abs(G - Gr))) <= 1e-5
    assert float(jnp.max(jnp.abs(R - Rr))) <= 1e-5


@pytest.mark.parametrize("N,L,D,m", [(40, 70, 2, 3), (16, 129, 1, 2),
                                     (9, 32, 3, 4)])
def test_gram_batched_one_launch_matches_vmapped_ref(N, L, D, m):
    """The agent-batched launch (grid (m, tri, n)) must equal the m
    independent reference Grams, padding edge cases included."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(m * N + L))
    H = jax.random.normal(k1, (m, N, L))
    T = jax.random.normal(k2, (m, N, D))
    G, R = gram_batched(H, T, block_l=32, block_n=16)
    Gr, Rr = jax.vmap(gram_ref)(H, T)
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gr), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(R), np.asarray(Rr), rtol=2e-4,
                               atol=2e-4)
    # exact block-level symmetry survives the batch axis
    np.testing.assert_array_equal(np.asarray(G),
                                  np.asarray(jnp.swapaxes(G, -1, -2)))


def test_gram_bf16_precision_documented_tolerance():
    """precision="bf16" streams H/T tiles in bf16 with fp32 accumulators:
    documented tolerance is 3e-2 RELATIVE on G and R (8-bit mantissa =>
    ~4e-3 typical, 3e-2 worst-case band), and fp32 stays exact."""
    m, N, L, D = 2, 64, 48, 2
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    H = jax.random.normal(k1, (m, N, L))
    T = jax.random.normal(k2, (m, N, D))
    Gr, Rr = jax.vmap(gram_ref)(H, T)
    Gb, Rb = gram_batched(H, T, block_l=16, block_n=32, precision="bf16")
    scale_g = float(jnp.max(jnp.abs(Gr)))
    scale_r = float(jnp.max(jnp.abs(Rr)))
    assert float(jnp.max(jnp.abs(Gb - Gr))) <= 3e-2 * scale_g
    assert float(jnp.max(jnp.abs(Rb - Rr))) <= 3e-2 * scale_r
    # and the knob rejects unknown modes
    with pytest.raises(ValueError, match="precision"):
        gram_batched(H, T, precision="fp8")


# ----------------------------- swa -----------------------------------------

@pytest.mark.parametrize("S,window,bq", [(64, 16, 16), (128, 33, 32),
                                         (128, 128, 32), (96, 200, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_sweep(S, window, bq, dtype):
    B, H, KV, D = 2, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(S + window), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, D), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, D), dtype)
    out = swa_attention(q, k, v, window=window, block_q=bq, block_k=bq)
    ref = swa_ref(q, k, v, window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
    )


def test_swa_mqa():
    """KV=1 (MQA, recurrentgemma's local attention)."""
    B, H, S, D = 1, 4, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, 1, S, D))
    v = jax.random.normal(ks[2], (B, 1, S, D))
    out = swa_attention(q, k, v, window=24, block_q=16, block_k=16)
    ref = swa_ref(q, k, v, 24)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)


# ----------------------------- rglru ---------------------------------------

@pytest.mark.parametrize("S,D,bs,bd", [(64, 32, 16, 16), (100, 48, 32, 32),
                                       (17, 130, 8, 64)])
def test_rglru_sweep(S, D, bs, bd):
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(S * D), 3)
    log_a = -jax.nn.softplus(jax.random.normal(ks[0], (B, S, D)))
    b = jax.random.normal(ks[1], (B, S, D))
    h0 = jax.random.normal(ks[2], (B, D))
    out = rglru_scan(log_a, b, h0, block_s=bs, block_d=bd)
    ref = rglru_scan_ref(log_a, b, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


def test_rglru_identity_decay():
    """log_a = 0 => pure cumulative sum of b plus h0."""
    B, S, D = 1, 20, 8
    b = jax.random.normal(jax.random.PRNGKey(1), (B, S, D))
    h0 = jnp.ones((B, D))
    out = rglru_scan(jnp.zeros((B, S, D)), b, h0, block_s=8, block_d=8)
    expect = jnp.cumsum(b, axis=1) + h0[:, None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-5,
                               atol=1e-5)


# ------------------------ bench snapshot writer ----------------------------

def test_bench_snapshot_single_writer_copies_identical(tmp_path):
    """BENCH_kernels.json bugfix: the snapshot has ONE writer that
    serializes once and byte-copies to the mirror path, so the two
    locations cannot drift."""
    from benchmarks.kernels import write_bench_snapshot

    canonical = tmp_path / "experiments" / "BENCH_kernels.json"
    mirror = tmp_path / "BENCH_kernels.json"
    results = {"schema": "bench_kernels/v2", "timings": [{"name": "x"}]}
    out = write_bench_snapshot(results, canonical=canonical, mirror=mirror)
    assert out == canonical
    assert canonical.read_bytes() == mirror.read_bytes()
    import json
    assert json.loads(canonical.read_text()) == results


def test_committed_bench_snapshots_identical():
    """The committed repo-root mirror must be byte-identical to the
    canonical experiments/benchmarks/ snapshot (i.e. both came out of the
    single writer on the last bench run)."""
    from benchmarks.kernels import BENCH_JSON, ROOT_BENCH_JSON

    assert BENCH_JSON.exists() and ROOT_BENCH_JSON.exists()
    assert BENCH_JSON.read_bytes() == ROOT_BENCH_JSON.read_bytes()


def test_bench_history_appends_dated_lines(tmp_path):
    """Every snapshot write appends ONE schema-versioned JSON line to the
    history file next to the canonical path: two writes -> two lines, each
    dated, the last line's results byte-equal to the snapshot contents."""
    import json
    from benchmarks.kernels import BENCH_HISTORY, write_bench_snapshot

    canonical = tmp_path / "experiments" / "BENCH_kernels.json"
    mirror = tmp_path / "BENCH_kernels.json"
    history = canonical.parent / BENCH_HISTORY.name
    r1 = {"schema": "bench_kernels/v3", "timings": [{"name": "a"}]}
    r2 = {"schema": "bench_kernels/v3", "timings": [{"name": "b"}]}
    write_bench_snapshot(r1, canonical=canonical, mirror=mirror)
    write_bench_snapshot(r2, canonical=canonical, mirror=mirror)
    lines = history.read_text().splitlines()
    assert len(lines) == 2
    for line in lines:
        entry = json.loads(line)
        assert entry["schema"] == "bench_history/v1"
        assert entry["date"]  # ISO stamp present
    last = json.loads(lines[-1])
    assert last["results"] == r2
    assert last["results"] == json.loads(canonical.read_text())
    assert canonical.read_bytes() == mirror.read_bytes()


def test_committed_bench_history_consistent_with_snapshot():
    """The committed history's LAST kernel-suite entry must be the committed
    snapshot — i.e. both artifacts came out of the same (final) kernel bench
    run.  The history file is shared with other suites (robustness appends
    ``{"robustness": ...}`` results lines), so the invariant binds the last
    entry whose results carry a ``bench_kernels/*`` schema, not the last
    line outright."""
    import json
    from benchmarks.kernels import BENCH_HISTORY, BENCH_JSON

    assert BENCH_HISTORY.exists()
    lines = BENCH_HISTORY.read_text().splitlines()
    assert len(lines) >= 1
    kernel_entries = []
    for line in lines:
        entry = json.loads(line)
        assert entry["schema"] == "bench_history/v1"
        assert entry["date"]
        results = entry["results"]
        if str(results.get("schema", "")).startswith("bench_kernels/"):
            kernel_entries.append(results)
    assert kernel_entries, "no kernel-suite entry in the committed history"
    assert kernel_entries[-1] == json.loads(BENCH_JSON.read_text())


# ------------------------ fused feature->Gram ------------------------------

def test_fused_activations_registry_matches_elm():
    """The in-kernel activation table must stay in lockstep with the ELM
    feature-map registry: same names, same callables."""
    from repro.core.elm import ACTIVATIONS as ELM_ACTS
    from repro.kernels.gram.kernel import ACTIVATIONS as KERNEL_ACTS

    assert KERNEL_ACTS.keys() == ELM_ACTS.keys()
    for name in ELM_ACTS:
        assert KERNEL_ACTS[name] is ELM_ACTS[name], name


def _tiled_hidden(X, fmap, block_n, block_l):
    """The materialized hidden layer built tile by tile, each tile the same
    (block_n, d_in) x (d_in, block_l) product the fused kernel computes.
    XLA's CPU GEMM picks its blocking from the matrix size, so one tile of
    a whole-matrix ``X @ W`` can differ from the tile product in the last
    bits; building H from the kernel's own tile shapes keeps the bitwise
    comparison about the kernel (grid, masks, accumulation order)."""
    from repro.core.elm import ACTIVATIONS
    from repro.kernels.gram.ops import resolve_block_n

    m, N, _ = X.shape
    L = fmap.W.shape[1]
    bn = resolve_block_n(N, block_n)
    Xp = jnp.pad(X, ((0, 0), (0, (-N) % bn), (0, 0)))
    Wp = jnp.pad(fmap.W, ((0, 0), (0, (-L) % block_l)))
    bp = jnp.pad(fmap.b, (0, (-L) % block_l))
    act = ACTIVATIONS[fmap.activation]
    tile = jax.jit(lambda x, w, b: act(jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b))
    H = jnp.stack([
        jnp.concatenate([
            jnp.concatenate([
                tile(Xp[a, n:n + bn], Wp[:, c:c + block_l],
                     bp[c:c + block_l])
                for c in range(0, Wp.shape[1], block_l)], axis=1)
            for n in range(0, Xp.shape[1], bn)], axis=0)
        for a in range(m)])
    return H[:, :N, :L]


_FUSED_SHAPES = [
    (2, 64, 16, 32), (1, 5, 3, 16), (2, 33, 8, 40),
    (1, 100, 36, 70), (2, 7, 11, 200),
]


def _fused_case(m, N, d_in, L, activation):
    from repro.core.elm import make_feature_map

    kx, kf, kt = jax.random.split(jax.random.PRNGKey(m * N + d_in + L), 3)
    X = jax.random.normal(kx, (m, N, d_in)) / jnp.sqrt(max(d_in, 1))
    fmap = make_feature_map(kf, d_in, L, activation=activation)
    T = jax.random.normal(kt, (m, N, 4))
    return X, fmap, T


@pytest.mark.parametrize("m,N,d_in,L", _FUSED_SHAPES)
@pytest.mark.parametrize("activation", ["sigmoid", "tanh"])
def test_gram_fused_bitwise_vs_materialized_pallas(m, N, d_in, L, activation):
    """The fused kernel must agree BITWISE (tol 0.0) with the materialized
    triangular kernel at the same tiling in fp32 — same tiles, same
    accumulation order, with the hidden layer computed in-kernel instead of
    streamed.  Ragged N and L exercise the padded-grid masking: act(0) != 0
    for sigmoid, so any unmasked padding row/column poisons G."""
    from repro.kernels.gram.ops import gram_fused

    X, fmap, T = _fused_case(m, N, d_in, L, activation)
    Gm, Rm = gram_batched(fmap(X), T, block_l=32, block_n=32)
    Gf, Rf = gram_fused(X, fmap.W, fmap.b, T, activation=activation,
                        block_l=32, block_n=32)
    np.testing.assert_array_equal(np.asarray(Gf), np.asarray(Gm))
    np.testing.assert_array_equal(np.asarray(Rf), np.asarray(Rm))


@pytest.mark.parametrize("m,N,d_in,L", _FUSED_SHAPES)
@pytest.mark.parametrize("activation", ["sigmoid", "tanh"])
def test_gram_fused_bitwise_vs_tile_built_pallas(m, N, d_in, L, activation):
    """The fused kernel against the materialized triangular kernel fed an H
    built from the kernel's own (block_n, d_in) x (d_in, block_l) tile
    products: BITWISE, whatever GEMM blocking the backend picks for a
    whole-matrix ``X @ W`` — this isolates the kernel's grid, masks and
    accumulation order from the hidden layer's rounding."""
    from repro.kernels.gram.ops import gram_fused

    X, fmap, T = _fused_case(m, N, d_in, L, activation)
    Gm, Rm = gram_batched(_tiled_hidden(X, fmap, 32, 32), T, block_l=32,
                          block_n=32)
    Gf, Rf = gram_fused(X, fmap.W, fmap.b, T, activation=activation,
                        block_l=32, block_n=32)
    np.testing.assert_array_equal(np.asarray(Gf), np.asarray(Gm))
    np.testing.assert_array_equal(np.asarray(Rf), np.asarray(Rm))


def test_gram_fused_bf16_bitwise_vs_materialized_bf16():
    """bf16 fused == bf16 materialized, bitwise: the in-kernel hidden tiles
    round to bf16 exactly like the materialized stream's cast."""
    from repro.core.elm import make_feature_map
    from repro.kernels.gram.ops import gram_fused

    kx, kf, kt = jax.random.split(jax.random.PRNGKey(5), 3)
    X = jax.random.normal(kx, (2, 48, 16)) / 4.0
    fmap = make_feature_map(kf, 16, 64)
    T = jax.random.normal(kt, (2, 48, 3))
    Gm, Rm = gram_batched(fmap(X), T, block_l=32, block_n=32,
                          precision="bf16")
    Gf, Rf = gram_fused(X, fmap.W, fmap.b, T, block_l=32, block_n=32,
                        precision="bf16")
    np.testing.assert_array_equal(np.asarray(Gf), np.asarray(Gm))
    np.testing.assert_array_equal(np.asarray(Rf), np.asarray(Rm))


def test_gram_fused_2d_matches_oracle():
    """Single-matrix (2D) inputs take the singleton-batch path; the oracle
    relation fused_ref == ref-on-materialized-H holds by construction and
    the kernel must match it to fp32 tolerance."""
    from repro.core.elm import make_feature_map
    from repro.kernels.gram.ops import gram_fused
    from repro.kernels.gram.ref import gram_fused_ref

    kx, kf, kt = jax.random.split(jax.random.PRNGKey(9), 3)
    X = jax.random.normal(kx, (40, 12)) / 3.0
    fmap = make_feature_map(kf, 12, 48)
    T = jax.random.normal(kt, (40, 2))
    Gf, Rf = gram_fused(X, fmap.W, fmap.b, T, block_l=16, block_n=16)
    Go, Ro = gram_fused_ref(X, fmap.W, fmap.b, T)
    np.testing.assert_allclose(np.asarray(Gf), np.asarray(Go), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(Rf), np.asarray(Ro), rtol=1e-5,
                               atol=1e-5)


def test_kernel_backend_decides_on_cpu():
    """On the CPU kernels are interpreted and the stats producers default
    to the jnp reference; ``use_pallas=True`` asks for the kernel."""
    from repro.kernels import backend

    assert backend.interpret() is True
    assert backend.stats_use_pallas(None) is False
    assert backend.stats_use_pallas(False) is False
    assert backend.stats_use_pallas(True) is True


def test_gram_fused_rejects_int8():
    from repro.kernels.gram.ops import gram_fused

    X = jnp.ones((4, 8))
    W = jnp.ones((8, 16))
    with pytest.raises(ValueError, match="int8"):
        gram_fused(X, W, jnp.ones((16,)), jnp.ones((4, 2)),
                   precision="int8")


# ------------------------------ int8 stream --------------------------------

def test_gram_int8_requires_tri_variant():
    H = jnp.ones((16, 8))
    T = jnp.ones((16, 2))
    with pytest.raises(ValueError, match="tri"):
        gram(H, T, precision="int8", variant="dense")


def test_gram_int8_pallas_matches_emulation():
    """The int8 Pallas path must match the jnp quantize-dequantize
    emulation at the SAME quant_seed to fp32 sum-order tolerance: both
    consume identical quantized tiles, only the accumulation order
    differs."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    H = jax.random.normal(k1, (2, 96, 48)) / jnp.sqrt(96)
    T = jax.random.normal(k2, (2, 96, 3))
    Gq, Rq = gram_batched(H, T, block_l=32, block_n=32, precision="int8",
                          quant_seed=7)
    Ge, Re = gram_batched(H, T, block_l=32, block_n=32, precision="int8",
                          quant_seed=7, force_ref=True)
    np.testing.assert_allclose(np.asarray(Gq), np.asarray(Ge), atol=2e-5,
                               rtol=0)
    np.testing.assert_allclose(np.asarray(Rq), np.asarray(Re), atol=2e-5,
                               rtol=0)


@pytest.mark.parametrize("N,L", [(96, 48), (33, 40)])
def test_gram_int8_within_quantization_envelope(N, L):
    """Per-tile-scaled stochastic int8 on normalized features lands within
    a few percent of the fp32 Gram."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(N + L))
    H = jax.random.normal(k1, (1, N, L)) / jnp.sqrt(N)
    T = jax.random.normal(k2, (1, N, 3))
    Gq, Rq = gram_batched(H, T, block_l=32, block_n=32, precision="int8")
    Gr, Rr = jax.vmap(gram_ref)(H, T)
    g_scale = float(jnp.max(jnp.abs(Gr)))
    r_scale = float(jnp.max(jnp.abs(Rr)))
    assert float(jnp.max(jnp.abs(Gq - Gr))) <= 5e-2 * g_scale
    assert float(jnp.max(jnp.abs(Rq - Rr))) <= 5e-2 * r_scale


def test_gram_int8_stochastic_rounding_unbiased():
    """The estimator property that justifies stochastic rounding: averaging
    the int8 Gram over quant seeds converges on the fp32 truth (the
    mean error must drop well below the single-seed error — ~1/sqrt(k)
    scaling), while round-to-nearest would keep a fixed bias."""
    n_seeds = 32
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    H = jax.random.normal(k1, (1, 64, 32)) / jnp.sqrt(64)
    T = jax.random.normal(k2, (1, 64, 2))
    Gr, _ = jax.vmap(gram_ref)(H, T)
    gs = [gram_batched(H, T, block_l=16, block_n=32, precision="int8",
                       quant_seed=s, force_ref=True)[0]
          for s in range(n_seeds)]
    single_errs = [float(jnp.max(jnp.abs(g - Gr))) for g in gs]
    mean_err = float(jnp.max(jnp.abs(sum(gs) / n_seeds - Gr)))
    assert mean_err < 0.5 * (sum(single_errs) / n_seeds), (
        mean_err, single_errs)


def test_quantize_dequantize_zero_padding_exact():
    """Zero entries (the kernel's padding) must quantize to exactly 0 so
    padded tiles contribute nothing."""
    from repro.kernels.gram.ops import quantize_dequantize

    H = jnp.zeros((1, 20, 24))
    Hdq = quantize_dequantize(H, block_l=16, block_n=16, quant_seed=0)
    np.testing.assert_array_equal(np.asarray(Hdq), np.zeros((1, 20, 24)))
