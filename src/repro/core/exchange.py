"""The neighbor-exchange layer: ONE communication abstraction, two backends.

Every executor in ``repro.core.engine`` ultimately does the same thing
between ``agent_update`` calls: collect the neighbor subspace views and
incoming edge duals an agent is entitled to see this round, reduce them
through ``cfg.aggregator``, and resolve the live degree / proximal weight.
Before this module that machinery was written five slightly different ways
(dense edge-list gathers, per-class colored gathers, ring ppermutes,
compiled-schedule ppermutes, and the netsim event-tape gather).  It now
lives here once, behind one contract:

    gather_views(published, duals, round_ctx) -> ExchangeViews

``published`` is whatever the backend serves views FROM (the live stacked
``U`` for fresh-view executors, the published-U ring buffer ``hist`` for
tape replay), ``duals`` the edge duals in the caller's layout, and
``round_ctx`` the per-tick tape rows (``None`` for synchronous fresh-view
exchange).  The result carries the reduced ``neigh`` aggregate (always
``deg_eff * center`` so the solver body downstream is untouched), the
``C_t^T lambda`` gather, the live degree, the resolved proximal weight,
and the candidate ``(table, mask)`` pair that fed ``cfg.aggregator`` on
the robust path.

Two interchangeable backends:

``DenseExchange``
    Edge-list segment sums + padded gather tables for the vmap executors
    (``fit_dense`` / ``fit_colored`` / southwell / ``fit_async``).  The
    mean path keeps the exact pre-existing two-segment-sum reduce (the
    bitwise oracle pinned by ``tests/test_golden_paths.py``); the robust
    path gathers a padded ``(m, K, L, r)`` candidate tensor + own U.
    ``DenseTapeGather`` extends it with the event-tape semantics: ring-
    buffer age selection per directed edge, sender-side adversary
    corruption (:func:`apply_attack`), membership degree masking, and the
    per-delivery candidate table of the robust path.

``ShardedGraphExchange``
    Masked-ppermute rounds over a compiled :class:`~repro.core.graph.
    EdgeSchedule` for the shard_map executors — one bidirectional partial
    ppermute per edge-color round, duals shipped source→dest over
    ``dir_perms``, round-mask-aware robust stacking.  Its tape driver
    replays an ``EventTape``/``AdversaryTape`` INSIDE the mesh: each shard
    carries a depth-D ring buffer of its OWN published U through the scan,
    age-selects the view it sends per round (send-side, so one ppermute
    still moves every message), corrupts it with its own attack code, and
    masks receptions by per-round edge-liveness — Byzantine + churn replay
    on real device meshes.

The ring/torus fast path (``fit_sharded``) keeps its specialized per-axis
ppermute loop in ``engine.ring_iteration`` (its exchange is three fixed
permutes, not a schedule), but shares :func:`stack_ring_candidates` for
the robust reduce, so the aggregator contract still lands here once.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class ExchangeViews(NamedTuple):
    """What one exchange round hands the update body (the contract)."""

    neigh: jax.Array            # deg_eff-weighted neighbor aggregate
    ct_lam: jax.Array           # C_t^T lambda gather
    deg_eff: jax.Array          # live degree (== static degree w/o churn)
    tau_eff: jax.Array          # proximal weight resolved vs deg_eff
    center: jax.Array | None    # neigh / deg or robust center (join starts)
    table: jax.Array | None     # robust candidate views fed to aggregator
    mask: jax.Array | None      # candidate validity mask ({0,1})


def neighbor_table(g):
    """Host-side padded adjacency table: (nbr_idx, nbr_mask) numpy arrays of
    shape (m, K_max) — the gather layout the robust aggregators consume."""
    nbrs: list[list[int]] = [[] for _ in range(g.m)]
    for s, e in g.edges:
        nbrs[s].append(e)
        nbrs[e].append(s)
    K = max((len(x) for x in nbrs), default=1) or 1
    nbr_idx = np.zeros((g.m, K), np.int32)
    nbr_mask = np.zeros((g.m, K), np.float32)
    for t, lst in enumerate(nbrs):
        nbr_idx[t, : len(lst)] = lst
        nbr_mask[t, : len(lst)] = 1.0
    return nbr_idx, nbr_mask


def delivery_table(g):
    """Host-side padded per-receiver table over the 2E directed deliveries
    (rows [0, E) = the e→s views to src, rows [E, 2E) = the s→e views to
    dst) — the tape-replay robust candidate layout."""
    recv = np.concatenate([
        np.asarray([e[0] for e in g.edges], np.int64),
        np.asarray([e[1] for e in g.edges], np.int64),
    ])
    rows: list[list[int]] = [[] for _ in range(g.m)]
    for i, t in enumerate(recv):
        rows[int(t)].append(i)
    K_pad = max((len(x) for x in rows), default=1) or 1
    pad_np = np.zeros((g.m, K_pad), np.int32)
    pmask_np = np.zeros((g.m, K_pad), np.float32)
    for t, lst in enumerate(rows):
        pad_np[t, : len(lst)] = lst
        pmask_np[t, : len(lst)] = 1.0
    return pad_np, pmask_np


def apply_attack(v, code_b, noise, replay, offset):
    """The Byzantine wire-corruption chain, shared by every tape driver.

    ``code_b`` broadcasts against ``v``: 1 = sign_flip, 2 = +noise,
    3 = publish ``replay`` (the initial view; the ZERO dual for shipped
    duals), 4 = +``offset`` (the shared colluding direction).  Code 0
    passes through untouched.
    """
    out = jnp.where(code_b == 1, -v, v)
    out = jnp.where(code_b == 2, v + noise, out)
    out = jnp.where(code_b == 3, replay, out)
    return jnp.where(code_b == 4, v + offset, out)


def stack_ring_candidates(views, U, deg, agg, dtype):
    """Robust reduce for the torus fast path: the per-axis ppermute views
    + own U as candidates (every ring neighbor is live → all-ones mask),
    rescaled back to the degree-weighted sum ``agent_update`` expects."""
    V = jnp.stack(list(views) + [U], axis=0)            # (K+1, L, r)
    Mv = jnp.ones((V.shape[0],), dtype)
    return deg * agg(V, Mv)


def aggregator_audit(V, M, center):
    """Telemetry: per-candidate Byzantine-rejection flags of one robust
    reduce (the ``agg_rejected`` counter's definition, shared by every
    executor).

    A candidate is flagged *rejected* when its Frobenius distance to the
    robust ``center`` is a distance outlier among the valid neighbor
    candidates: more than 10x the masked median distance AND above an
    absolute floor of ``1e-6 * (1 + ||center||_F)``.  The trailing
    candidate (every table builder appends own U last) is excluded —
    the audit is about *messages*, not the local iterate.  Both gates
    make a clean federation audit to an exact zero: identical early-tick
    candidates have distance 0 (fails ``> 10 * median``), and a
    converged spread sits under the absolute floor.  ``V`` is
    ``(..., K, L, r)``, ``M`` ``(..., K)``; returns {0,1} flags of shape
    ``(..., K)`` in ``V.dtype`` for the caller to sum.
    """
    d = jnp.sqrt(jnp.sum((V - center[..., None, :, :]) ** 2, axis=(-2, -1)))
    K = V.shape[-3]
    valid = (M > 0) & (jnp.arange(K) < K - 1)
    big = jnp.asarray(jnp.finfo(d.dtype).max, d.dtype)
    ds = jnp.sort(jnp.where(valid, d, big), axis=-1)
    n = jnp.maximum(jnp.sum(valid, axis=-1).astype(jnp.int32), 1)
    lo = jnp.take_along_axis(ds, ((n - 1) // 2)[..., None], axis=-1)[..., 0]
    hi = jnp.take_along_axis(ds, (n // 2)[..., None], axis=-1)[..., 0]
    med = 0.5 * (lo + hi)
    floor = 1e-6 * (1.0 + jnp.sqrt(jnp.sum(center**2, axis=(-2, -1))))
    rej = valid & (d > 10.0 * med[..., None]) & (d > floor[..., None])
    return rej.astype(V.dtype)


@jax.tree_util.register_pytree_node_class
class DenseExchange:
    """Backend 1: edge-list gathers for the single-program executors.

    The mean path (``agg is None``) keeps the exact two-segment-sum adds
    of the pre-refactor executors — for degree-2 graphs those are the same
    two-term additions the ring executor performs, so the executors stay
    bitwise-aligned far longer than matmul gathering would.

    A pytree: its index and degree arrays are the leaves, ``m``, the
    aggregator and the dtype the static part, so a compiled program can
    take the exchange as an argument instead of closing over its arrays.
    """

    def __init__(self, g, dtype, agg: Callable | None):
        self.m = g.m
        self.src = jnp.asarray([e[0] for e in g.edges], jnp.int32)
        self.dst = jnp.asarray([e[1] for e in g.edges], jnp.int32)
        self.deg = jnp.asarray(g.degrees(), dtype=dtype)
        self.agg = agg
        self.dtype = dtype
        if agg is not None:
            nbr_idx_np, nbr_mask_np = neighbor_table(g)
            self.nbr_idx = jnp.asarray(nbr_idx_np)
            self.nbr_mask = jnp.asarray(nbr_mask_np, dtype)
            self.ones_m1 = jnp.ones((g.m, 1), dtype)

    def tree_flatten(self):
        leaves = (self.src, self.dst, self.deg)
        if self.agg is not None:
            leaves += (self.nbr_idx, self.nbr_mask, self.ones_m1)
        return leaves, (self.m, self.agg, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        ex = object.__new__(cls)
        ex.m, ex.agg, ex.dtype = aux
        ex.src, ex.dst, ex.deg = leaves[:3]
        if ex.agg is not None:
            ex.nbr_idx, ex.nbr_mask, ex.ones_m1 = leaves[3:]
        return ex

    def edge_diff(self, x):
        """C x per edge: x[s] - x[e] for every edge (s, e)."""
        return x[self.src] - x[self.dst]

    def neighbor_sum(self, U):
        """Fresh-view neighbor reduce: plain segment sums (mean) or the
        padded candidate gather + own U through the aggregator."""
        if self.agg is None:
            return jax.ops.segment_sum(
                U[self.dst], self.src, self.m
            ) + jax.ops.segment_sum(U[self.src], self.dst, self.m)
        V = jnp.concatenate([U[self.nbr_idx], U[:, None]], axis=1)
        Mv = jnp.concatenate([self.nbr_mask, self.ones_m1], axis=1)
        return self.deg[:, None, None] * self.agg(V, Mv)

    def ct_transpose(self, lam):
        """C_t^T lambda: +lam on edges where t is the source, - where end."""
        return jax.ops.segment_sum(
            lam, self.src, self.m
        ) - jax.ops.segment_sum(lam, self.dst, self.m)

    def audit(self, U):
        """Telemetry (robust path only): rebuild this round's candidate
        table and count :func:`aggregator_audit` rejections — a scalar."""
        V = jnp.concatenate([U[self.nbr_idx], U[:, None]], axis=1)
        Mv = jnp.concatenate([self.nbr_mask, self.ones_m1], axis=1)
        return jnp.sum(aggregator_audit(V, Mv, self.agg(V, Mv)))

    def gather_views(self, published, duals, round_ctx=None) -> ExchangeViews:
        """The exchange contract, fresh-view form (``round_ctx=None``):
        ``published`` is the live stacked U.  Tape-driven gathers go
        through :class:`DenseTapeGather`, which binds the ring buffer and
        tape rows into the same result type."""
        if round_ctx is not None:
            raise ValueError(
                "DenseExchange serves fresh views; use DenseTapeGather "
                "for tape-driven (round_ctx) gathers"
            )
        return ExchangeViews(
            neigh=self.neighbor_sum(published),
            ct_lam=self.ct_transpose(duals),
            deg_eff=self.deg,
            tau_eff=None,
            center=None,
            table=None,
            mask=None,
        )


class DenseTapeCtx(NamedTuple):
    """Per-tick tape rows for :class:`DenseTapeGather` (``xs`` of the async
    scan): the EventTape rows, plus the AdversaryTape rows when present."""

    age_k: jax.Array                 # (2, E) int32
    k: jax.Array                     # ()  absolute tick
    code_k: jax.Array | None = None  # (m,) attack codes
    noise_k: jax.Array | None = None
    member_k: jax.Array | None = None


class DenseTapeGather:
    """Event-tape view gather over a :class:`DenseExchange` (executor 5).

    Serves each directed edge the aged view the tape dictates (ring-buffer
    slot ``(k - age) mod depth``), applies the sender's wire corruption,
    masks dead edges out of every reduction, and resolves the live degree
    / scalar-tau proximal weight.  Op-for-op the gather the netsim
    executor ran before the exchange refactor (the sha256 oracle covers
    it), now shared so the in-mesh tape driver has one reference."""

    def __init__(self, ex: DenseExchange, g, cfg, depth: int, is_adv: bool,
                 init_U, offset, tau_t):
        self.ex = ex
        self.depth = depth
        self.is_adv = is_adv
        self.init_U = init_U
        self.offset = offset
        self.scalar_tau = jnp.asarray(cfg.tau).ndim == 0
        self.tau0 = jnp.asarray(cfg.tau, ex.dtype)
        self.tau_t = tau_t  # the per-agent resolved weight (full membership)
        if ex.agg is not None:
            pad_np, pmask_np = delivery_table(g)
            self.pad_idx = jnp.asarray(pad_np)
            self.pad_mask = jnp.asarray(pmask_np, ex.dtype)
            self.ones_m1 = jnp.ones((g.m, 1), ex.dtype)

    def __call__(self, hist, U, ctx: DenseTapeCtx):
        """-> (views (view0, view1), ExchangeViews-without-ct_lam fields).

        ``ct_lam`` needs the dual mode (live vs aged), so it is gathered
        separately by the executor; this returns ``(view0, view1, neigh,
        center, deg_eff, tau_eff, el)`` with ``el`` the per-edge live mask
        (None without an adversary tape)."""
        ex = self.ex
        src, dst, m = ex.src, ex.dst, ex.m
        slot0 = jnp.mod(ctx.k - ctx.age_k[0], self.depth)   # e -> s views
        slot1 = jnp.mod(ctx.k - ctx.age_k[1], self.depth)   # s -> e views
        view0 = hist[slot0, dst]                            # (E, L, r)
        view1 = hist[slot1, src]
        if self.is_adv:
            code_k, noise_k, member_k = ctx.code_k, ctx.noise_k, ctx.member_k

            def corrupt(v, c, sender):
                return apply_attack(
                    v, c[:, None, None], noise_k[sender],
                    self.init_U[sender], self.offset,
                )

            view0 = corrupt(view0, code_k[dst], dst)
            view1 = corrupt(view1, code_k[src], src)
            el = member_k[src] * member_k[dst]              # (E,)
            elb = el[:, None, None]
            deg_eff = jax.ops.segment_sum(
                el, src, m
            ) + jax.ops.segment_sum(el, dst, m)
            tau_eff = self.tau0 + deg_eff if self.scalar_tau else self.tau_t
            v0, v1 = view0 * elb, view1 * elb
        else:
            el = None
            deg_eff, tau_eff = ex.deg, self.tau_t
            v0, v1 = view0, view1
        if ex.agg is None:
            neigh = jax.ops.segment_sum(
                v0, src, m
            ) + jax.ops.segment_sum(v1, dst, m)
            center = (
                neigh / jnp.maximum(deg_eff, 1.0)[:, None, None]
                if self.is_adv else None
            )
            table = mask = None
        else:
            W = jnp.concatenate([view0, view1], axis=0)     # (2E, L, r)
            mv = self.pad_mask
            if self.is_adv:
                live2 = jnp.concatenate([el, el])
                mv = mv * live2[self.pad_idx]
            table = jnp.concatenate([W[self.pad_idx], U[:, None]], axis=1)
            mask = jnp.concatenate([mv, self.ones_m1], axis=1)
            center = ex.agg(table, mask)
            neigh = deg_eff[:, None, None] * center
        views = ExchangeViews(
            neigh=neigh, ct_lam=None, deg_eff=deg_eff, tau_eff=tau_eff,
            center=center, table=table, mask=mask,
        )
        return view0, view1, slot1, el, views


class ShardedGraphExchange:
    """Backend 2: masked-ppermute rounds over a compiled edge schedule.

    Construction is host-side (the schedule, the per-shard round tables);
    the ``exchange`` / ``reduce_views`` / ``ship_ct_lam`` methods run
    INSIDE shard_map on shard-local blocks.  The mean path keeps the
    pre-existing ``functools.reduce(jnp.add, ...)`` round-order sum (the
    sha256 oracle); the robust path stacks the per-round views + own U
    with the round-participation mask so idle-round zeros are EXCLUDED,
    never treated as candidates.
    """

    def __init__(self, g, sched, axes_t: Sequence[str], dtype,
                 agg: Callable | None):
        self.g = g
        self.sched = sched
        self.axes_t = tuple(axes_t)
        self.dtype = dtype
        self.agg = agg
        self.n_rounds = sched.n_rounds
        # round-participation mask: rmask[t, rr] = 1 iff round rr delivers
        # a partner's U to agent t; sum over rounds equals the degree
        rmask_rows = [[0.0] * self.n_rounds for _ in range(g.m)]
        for rr in range(self.n_rounds):
            for _s, dd in sched.bidir_perms[rr]:
                rmask_rows[dd][rr] = 1.0
        self.rmask_all = jnp.asarray(rmask_rows, dtype)     # (m, rounds)

    def exchange(self, x):
        """One bidirectional ppermute per edge-color round: round r
        delivers the round-r matched partner's x (zeros when idle)."""
        return [
            jax.lax.ppermute(x, self.axes_t, self.sched.bidir_perms[rr])
            for rr in range(self.n_rounds)
        ]

    def reduce_views(self, nb, U, deg_t, rmask):
        """Per-round neighbor views -> the agent_update neigh_sum: the
        plain sum (mean path, bitwise the pre-existing reduce), or the
        robust center over round-live views + own U, degree-rescaled."""
        if self.agg is None:
            return functools.reduce(jnp.add, nb)
        V = jnp.stack(list(nb) + [U], axis=0)       # (rounds + 1, L, r)
        Mv = jnp.concatenate([rmask, jnp.ones((1,), self.dtype)])
        return deg_t * self.agg(V, Mv)

    def audit_views(self, nb, U, rmask, center):
        """Telemetry (robust path only): shard-local rejection count of
        one :func:`aggregator_audit` pass over the per-round views + own
        U — ``rmask`` is the round-live mask (the participation mask on
        the no-tape path, the tape ``live`` row under replay)."""
        V = jnp.stack(list(nb) + [U], axis=0)
        Mv = jnp.concatenate([rmask, jnp.ones((1,), self.dtype)])
        return jnp.sum(aggregator_audit(V, Mv, center))

    def ship_ct_lam(self, lam, slots, own):
        """C_t^T lambda: + the duals this shard owns (unowned slots stay
        zero), - every incoming dual, shipped source->dest per round."""
        ct_lam = jnp.sum(lam, axis=0)
        for rr in range(self.n_rounds):
            lam_send = own[rr] * lam[slots[rr]]
            ct_lam = ct_lam - jax.lax.ppermute(
                lam_send, self.axes_t, self.sched.dir_perms[rr]
            )
        return ct_lam

    # ---------------------------------------------------------------- tape

    def tape_tables(self, tape) -> dict:
        """Host-side per-(tick, agent, round) tables driving in-mesh replay.

        ``send_age[k, t, rr]`` is the age of the message agent ``t`` SENDS
        on its round-``rr`` edge at tick ``k`` (the tape row of that
        directed edge): the sender reads ring slot ``(k - send_age) mod
        depth`` of its OWN published history, so one ppermute still moves
        every message and no receiver ever indexes a foreign buffer.
        ``live[k, t, rr]`` masks the round for BOTH endpoints when either
        is a non-member at tick ``k`` (zero rows on idle rounds double as
        the round-participation mask).
        """
        g, sched = self.g, self.sched
        iters, m = tape.iters, g.m
        age = np.asarray(tape.age)
        member = getattr(tape, "member", None)
        member = (
            np.ones((iters, m), np.float32) if member is None
            else np.asarray(member, np.float32)
        )
        send_age = np.ones((iters, m, self.n_rounds), np.int32)
        live = np.zeros((iters, m, self.n_rounds), np.float32)
        for rr, cls in enumerate(sched.rounds):
            for i in cls:
                s, e = g.edges[i]
                # direction 1 is s -> e: s's outgoing age; 0 is e -> s
                send_age[:, s, rr] = age[:, 1, i]
                send_age[:, e, rr] = age[:, 0, i]
                el = member[:, s] * member[:, e]
                live[:, s, rr] = el
                live[:, e, rr] = el
        member_prev = (
            np.concatenate([member[:1], member[:-1]], axis=0)
            if iters else member
        )
        return {
            "send_age": send_age,
            "live": live,
            "member": member,
            "member_prev": member_prev,
        }

    def tape_exchange(self, hist, k, age_row, depth, code=None, noise_t=None,
                      offset=None, init_u=None):
        """Send-side aged (and adversary-corrupted) neighbor exchange: per
        round the sender age-selects from its OWN ring buffer, corrupts
        with its OWN attack code, and the bidirectional ppermute delivers.
        Receptions on idle/dead rounds are masked by the caller via the
        ``live`` row."""
        outs = []
        for rr in range(self.n_rounds):
            v = hist[jnp.mod(k - age_row[rr], depth)]
            if code is not None:
                v = apply_attack(v, code, noise_t, init_u, offset)
            outs.append(
                jax.lax.ppermute(v, self.axes_t, self.sched.bidir_perms[rr])
            )
        return outs

    def tape_ct_lam(self, lam, slots, own, live_row, *, aged=None):
        """C_t^T lambda under membership masking: + the owned duals with
        dead owned edges removed (``(own - gate)`` is an EXACT zero when
        the edge is live, so a zero-adversary tape keeps the no-tape
        gather's values bitwise), - the received duals, sender-masked so a
        dead edge's dual leaves both sides.  ``aged`` (a dict with
        lam_hist/k/age_row/depth and optional code/noise/offset) switches
        the shipped dual to the age-selected, sender-corrupted ``lam_hist``
        slot — the fully message-faithful ``aged_duals`` protocol in-mesh
        (a replayed dual is the ZERO initial dual)."""
        ct_lam = jnp.sum(lam, axis=0)
        for rr in range(self.n_rounds):
            gate = own[rr] * live_row[rr]
            ct_lam = ct_lam - (own[rr] - gate) * lam[slots[rr]]
            if aged is None:
                lam_send = gate * lam[slots[rr]]
            else:
                slot = jnp.mod(aged["k"] - aged["age_row"][rr],
                               aged["depth"])
                lv = aged["lam_hist"][slot, slots[rr]]
                if aged.get("code") is not None:
                    lv = apply_attack(
                        lv, aged["code"], aged["noise"],
                        jnp.zeros_like(lv), aged["offset"],
                    )
                lam_send = gate * lv
            ct_lam = ct_lam - jax.lax.ppermute(
                lam_send, self.axes_t, self.sched.dir_perms[rr]
            )
        return ct_lam
