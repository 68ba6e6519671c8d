"""MultiWrite collectives as JAX ``shard_map`` programs (TPU adaptation).

The paper implements MultiWrite as NPU-side software relaying: one copy of
each datum crosses the bottleneck link, and the landing ("same-index") node
replicates it locally (§3.2, §4.3.3).  TPUs expose no raw point-to-point
sends, so the recursive replication tree maps onto a *two-level collective
schedule* (DESIGN.md §2):

  stage 1  move exactly ONE copy of each datum across the slow axis
           (``pod``/DCN, or the cross-domain pair link in the split-TP
           scenario) — ``lax.ppermute`` / ``lax.all_to_all`` on that axis;
  stage 2  replicate at the landing chip with fast-axis collectives —
           the relay's packet copy/forward loop (cs_relay) becomes bitmap-
           driven packing + an intra-pod ``all_to_all``.

Contents:

AllGather (paper §3.1 / §5.2):
  * :func:`planned_allgather`    — planner-selected scheme + split (the
    §5.2 dynamic workflow: baseline below the Fig 7 crossover, MultiWrite
    above it — no hard-coded ``mode=``/``split=`` at call sites).
  * :func:`multiwrite_allgather` — split-TP AllGather using idle
    cross-domain links, paired or full relaying, one cross copy per chunk.
  * :func:`allgather_reference`  — plain subgroup all_gather (baseline).

MoE dispatch/combine (paper §3.2 / §6.3):
  * :func:`route_topk`            — gate -> (gates, expert ids).
  * :func:`pack_by_bitmap`        — bitmap-driven send-buffer packing; the
    pure-jnp twin of the Pallas ``dispatch_pack`` kernel (cs_send).
  * :func:`dispatch_slots`        — each stage's slot count, a share of
    the rows that can arrive there at most (past it is refused).
  * :func:`capped_capacities`     — headroom capped at those worst cases.
  * :func:`hierarchical_dispatch` — MultiWrite dispatch: one copy per
    (token, remote pod), relay replication intra-pod.
  * :func:`baseline_dispatch`     — unicast dispatch: one copy per
    (token, destination chip) crosses the pod axis (redundant baseline).
  * :func:`hierarchical_combine` / :func:`baseline_combine` — return path;
    hierarchical combine adds *relay-side partial reduction* (beyond-paper:
    the dual of dispatch dedup — one partial per (token, pod) crosses back).
  * :func:`hierarchical_combine_unicast` — unicast return path for the
    hierarchical dispatch (no relay reduction): the executable lowering of
    the combine planner's "unicast" plan, selected at trace time through
    ``ParallelContext.moe_pipeline_kwargs`` (jointly with the dispatch
    scheme and the shared microbatch G).

All functions are pure and must be called inside ``shard_map`` (they use
named axes).  Shapes are static; capacity semantics follow standard MoE
practice (priority = token order, overflow dropped & masked).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from jax.lax import axis_size


# ===========================================================================
# AllGather over split TP domains (§3.1, §5.2)
# ===========================================================================

def _domain_groups(n: int, num_domains: int) -> list[list[int]]:
    d = n // num_domains
    return [list(range(i * d, (i + 1) * d)) for i in range(num_domains)]


def allgather_reference(x: jax.Array, axis_name: str,
                        num_domains: int = 2) -> jax.Array:
    """Baseline: all_gather over the local TP domain only (paper §5.2
    traditional workflow).  Returns [domain_size, *x.shape]."""
    n = axis_size(axis_name)
    groups = _domain_groups(n, num_domains)
    return lax.all_gather(x, axis_name, axis_index_groups=groups)


def multiwrite_allgather(x: jax.Array, axis_name: str, *,
                         num_domains: int = 2,
                         split: float = 0.5,
                         mode: str = "paired") -> jax.Array:
    """MultiWrite AllGather over a split-TP axis (paper §5.2 optimized).

    The axis of size ``n`` is split into ``num_domains`` equal TP domains
    (blocked).  Each chip all-gathers within its own domain, but routes a
    ``1 - split`` fraction of its fragment over the otherwise-idle
    cross-domain links: ONE copy to the same-index partner (the relay),
    which replicates to the source's domain peers — stage 1 + stage 2 of
    the MultiWrite tree.

    Args:
      x: local fragment, rank >= 1; the leading axis is split.
      axis_name: mesh axis carrying all domains (size = domain * num_domains).
      num_domains: number of TP domains sharing the axis (2 = paper §3.1).
      split: fraction sent over direct intra-domain links.  0.5 equalizes
        path times for the paired scheme (``optimal_split``); 1.0 degrades
        to the baseline.
      mode: "paired" (partner relays the whole cross chunk) or "full"
        (cross chunk sliced over every opposite-domain chip).

    Returns:
      [domain_size, *x.shape] — bit-identical to :func:`allgather_reference`.
    """
    if num_domains != 2:
        raise NotImplementedError("paired relaying is defined for 2 domains")
    n = axis_size(axis_name)
    half = n // 2
    rows = x.shape[0]
    cut = int(round(rows * split))
    cut = max(0, min(rows, cut))
    if cut == rows:  # pure baseline
        return allgather_reference(x, axis_name, num_domains)
    groups = _domain_groups(n, num_domains)
    xd, xc = x[:cut], x[cut:]

    # ---- direct part: intra-domain all_gather ------------------------------
    gd = lax.all_gather(xd, axis_name, axis_index_groups=groups)

    if mode == "paired":
        gc = _paired_relay_gather(xc, axis_name, n, half)
    elif mode == "full":
        gc = _full_relay_gather(xc, axis_name, n, half)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.concatenate([gd, gc], axis=1)


def planned_allgather(x: jax.Array, axis_name: str, *,
                      num_domains: int = 2,
                      planner=None, hw=None, decision=None) -> jax.Array:
    """AllGather whose scheme and split come from a planner decision
    (§5.2 dynamic workflow) instead of hard-coded ``mode=``/``split=``
    kwargs.

    ``decision`` is the per-site verdict of a bound
    :class:`~repro.core.plan.ExecutionPlan` (the declarative path —
    layers pass it through from ``ParallelContext.allgather_plan``).
    Without one, the process planner decides here: at trace time the
    fragment size and split-TP topology are static, so the (LRU-cached)
    decision selects among the registered executable plans — baseline
    below the Fig 7 crossover, multiwrite paired/full above it, at the
    split the latency model scored best.  Must be called inside
    ``shard_map``.
    """
    import math as _math

    from repro.core import planner as _planner_mod
    from repro.core.topology import split_tp_full_mesh

    if decision is None:
        n = axis_size(axis_name)
        frag_bytes = _math.prod(x.shape) * x.dtype.itemsize
        topo, _ = split_tp_full_mesh(n, tp=max(1, n // num_domains))
        pl = planner or _planner_mod.default_planner()
        decision = pl.choose("allgather", frag_bytes, topo, hw,
                             executable_only=True, num_domains=num_domains)
    kw = decision.shard_map_kwargs
    if kw["mode"] is None:
        return allgather_reference(x, axis_name, num_domains)
    return multiwrite_allgather(x, axis_name, num_domains=num_domains,
                                split=kw["split"], mode=kw["mode"])


def _paired_relay_gather(xc: jax.Array, axis_name: str, n: int,
                         half: int) -> jax.Array:
    """Stage 1: swap cross chunks with the same-index partner (ONE copy on
    each cross link).  Stage 2: each relay forwards its partner's chunk to
    the partner's domain peers, one ppermute round per peer offset —
    distinct physical links per round (§3.1 paired relaying)."""
    # stage 1: i <-> i+half
    swap = [(i, (i + half) % n) for i in range(n)]
    xr = lax.ppermute(xc, axis_name, swap)  # chunk of source partner(i)

    # stage 2: relay i holds source s(i) = (i+half)%n; peers of s(i) within
    # s(i)'s domain are offset r = 1..half-1.  Round r: relay i -> peer
    # (base(s)+ (idx(s)+r)%half).
    received = []
    for r in range(1, half):
        perm = []
        for i in range(n):
            s = (i + half) % n
            base, idx = (s // half) * half, s % half
            perm.append((i, base + (idx + r) % half))
        received.append(lax.ppermute(xr, axis_name, perm))
    # Rank j received, in round r, the cross chunk of source
    # base(j) + (idx(j) - r) % half.  Assemble domain-source order 0..half-1:
    me = lax.axis_index(axis_name)
    base, idx = (me // half) * half, me % half
    slots = [xc] + received          # slots[r] = source idx (idx - r) % half
    # gather into source order via a permutation matrix (static half x half
    # one-hot selected by the dynamic idx):
    stacked = jnp.stack(slots)       # [half, ...] in (idx - r) order
    offset = (idx - jnp.arange(half, dtype=idx.dtype)) % half  # src k at row?
    # slots[r] holds source (idx - r) % half -> source k sits at row
    # (idx - k) % half:
    rows_for_src = (idx - jnp.arange(half, dtype=idx.dtype)) % half
    del offset
    return stacked[rows_for_src]     # [half, ...] in source order


def _full_relay_gather(xc: jax.Array, axis_name: str, n: int,
                       half: int) -> jax.Array:
    """Full multi-path relaying (§3.1): the cross chunk is sliced over ALL
    ``half`` opposite-domain chips; each relay forwards its slice to the
    source's domain peers.

    Stage 1, round r: chip i sends slice ``(idx(i)+r) % half`` to the
    opposite-domain chip of that index — a true permutation per round, one
    slice copy per cross link.  After the rounds, relay j (index t) holds,
    from round r, slice t of the opposite source with index (t - r) % half.

    Stage 2, round (r, f) with f = 1..half-1: relay j forwards its round-r
    slice to the source's peer (source_domain, (t - r + f) % half).  Chip q
    (index iq) thereby receives, from round (r, f), slice (iq + r - f) %
    half of its domain-mate with index (iq - f) % half — every slice of
    every peer exactly once.  Per cross link: stage-1 one slice + stage-2
    (half-1) slices = (1-split)*s total, matching the §3.1 load derivation
    (r = 1/2 balance).
    """
    rows = xc.shape[0]
    pad = (-rows) % half
    if pad:
        xc = jnp.concatenate(
            [xc, jnp.zeros((pad,) + xc.shape[1:], xc.dtype)], axis=0)
    sliced = xc.reshape((half, xc.shape[0] // half) + xc.shape[1:])
    idx = lax.axis_index(axis_name) % half

    # ---- stage 1 ------------------------------------------------------------
    landed = []
    for r in range(half):
        perm = [(i, (((i // half) ^ 1) * half) + (i % half + r) % half)
                for i in range(n)]
        chunk = jnp.take(sliced, (idx + r) % half, axis=0)
        landed.append(lax.ppermute(chunk, axis_name, perm))

    # ---- stage 2 ------------------------------------------------------------
    out_rounds: list[list[jax.Array]] = [[] for _ in range(half)]  # per f
    for r in range(half):
        for f in range(1, half):
            perm = [(j, (((j // half) ^ 1) * half) + (j % half - r + f) % half)
                    for j in range(n)]
            out_rounds[f].append(lax.ppermute(landed[r], axis_name, perm))

    # ---- assembly -----------------------------------------------------------
    gathered = [sliced.reshape((-1,) + xc.shape[1:])]   # f = 0: own chunk
    for f in range(1, half):
        stacked = jnp.stack(out_rounds[f])               # [rounds r, ...]
        # round r carries slice (iq + r - f) % half -> slice sl sits at
        # round (sl - iq + f) % half:
        ordered = stacked[(jnp.arange(half) - idx + f) % half]
        gathered.append(ordered.reshape((-1,) + xc.shape[1:]))
    stackedg = jnp.stack(gathered)                       # [f, rows, ...]
    # gathered[f] = chunk of peer (iq - f) % half -> peer k at f=(iq-k)%half
    out = stackedg[(idx - jnp.arange(half, dtype=idx.dtype)) % half]
    if pad:
        out = out[:, :rows]
    return out


# ===========================================================================
# MoE routing
# ===========================================================================

def route_topk(logits: jax.Array, k: int,
               *, softmax_before_topk: bool = True
               ) -> tuple[jax.Array, jax.Array]:
    """Top-k gating. Returns (gates [.., k] f32 normalized, ids [.., k] i32)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, ids = lax.top_k(probs, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, ids.astype(jnp.int32)


# ===========================================================================
# Bitmap packing (cs_send analogue; jnp twin of the Pallas kernel)
# ===========================================================================

def pack_by_bitmap(tokens: jax.Array, bitmap: jax.Array, valid: jax.Array,
                   num_dests: int, capacity: int,
                   ) -> tuple[jax.Array, jax.Array]:
    """Pack rows into per-destination send buffers, bitmap-driven (§4.1).

    Args:
      tokens: [N, H] payload rows.
      bitmap: [N] int32 — bit d set ⇔ row goes to destination d (d < 32).
      valid:  [N] bool — row participates at all.
      num_dests: number of destinations D (<= 32).
      capacity: C, max rows per destination (overflow dropped, token order
        priority — standard MoE capacity semantics).

    Returns:
      out:     [D, C, H] packed rows (zeros where empty).
      src_idx: [D, C] int32 source row index, -1 where empty — the return
               map the combine path uses.
    """
    n, h = tokens.shape
    d_ids = jnp.arange(num_dests, dtype=jnp.int32)
    want = ((bitmap[None, :] >> d_ids[:, None]) & 1).astype(bool)  # [D, N]
    want = want & valid[None, :]
    pos = jnp.cumsum(want, axis=1) - 1                              # [D, N]
    keep = want & (pos < capacity)
    flat = jnp.where(keep, d_ids[:, None] * capacity + pos, num_dests * capacity)
    # one scatter over [D*C (+1 overflow slot)]
    src = jnp.full((num_dests * capacity + 1,), -1, jnp.int32)
    rows = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (num_dests, n))
    src = src.at[flat.reshape(-1)].set(rows.reshape(-1), mode="drop")
    src_idx = src[:num_dests * capacity].reshape(num_dests, capacity)
    gathered = jnp.where((src_idx >= 0)[..., None],
                         tokens[jnp.clip(src_idx, 0), :], 0)
    return gathered.astype(tokens.dtype), src_idx


def gather_rows(tokens: jax.Array, src_idx: jax.Array) -> jax.Array:
    """Gather rows by a pack map (-1 -> zeros)."""
    out = jnp.where((src_idx >= 0)[..., None],
                    tokens[jnp.clip(src_idx, 0)], 0)
    return out.astype(tokens.dtype)


# ===========================================================================
# Hierarchical (MultiWrite) MoE dispatch / combine
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class EPMesh:
    """Static description of the expert-parallel mesh slice."""
    pod_axis: str | None        # slow axis (DCN); None = single level
    ep_axis: str                # fast axis (ICI)
    num_pods: int
    ep_per_pod: int

    @property
    def num_ranks(self) -> int:
        return self.num_pods * self.ep_per_pod


@dataclasses.dataclass(frozen=True)
class DispatchConfig:
    """Slots of each dispatch stage, as a share (at most 1) of the rows
    that can arrive there at most: the stage's no-drop worst case.  A
    share of 1 drops nothing whatever the routing; below 1, rows past
    the slots are dropped in token order.  The dispatch refuses a share
    past 1; :func:`capped_capacities` turns headroom, whose shares may
    pass 1, into shares of the worst case."""
    num_experts: int
    top_k: int
    # stage 1 (MultiWrite, per pod) / unicast (per rank): of the N rows,
    # each sent once per destination at most
    pod_capacity: float = 1.0
    # stage 2 (MultiWrite, per ep rank): of the P*Cp stage-1 slots that
    # land on a relay, each forwarded once per ep rank at most
    ep_capacity: float = 1.0
    # per local expert: of the rows that reach the rank (D*Cd MultiWrite,
    # R*Cr unicast), each routed to an expert once at most
    expert_capacity: float = 1.0


def _stages(cfg: DispatchConfig, mesh: EPMesh, unicast: bool):
    """Each dispatch stage as (name, fan-in, share): the most rows that
    can arrive there are its fan-in times the slots of the stage before
    it (the ``n`` rows a chip, before the first)."""
    p, d = mesh.num_pods, mesh.ep_per_pod
    if unicast:
        return (("per-rank", 1, cfg.pod_capacity),
                ("expert", p * d, cfg.expert_capacity))
    return (("stage-1", 1, cfg.pod_capacity),
            ("stage-2", p, cfg.ep_capacity),
            ("expert", d, cfg.expert_capacity))


def dispatch_slots(cfg: DispatchConfig, mesh: EPMesh, n: int,
                   unicast: bool = False) -> tuple[int, ...]:
    """Slots each stage allocates for ``n`` rows a chip, at least one:
    (Cp, Cd, Ce) for :func:`hierarchical_dispatch`, (Cr, Ce) for the
    ``unicast`` :func:`baseline_dispatch`.  A share that would allocate
    past the stage's worst case is refused: such slots could never
    fill."""
    slots, prev = [], n
    for stage, fan, share in _stages(cfg, mesh, unicast):
        worst = fan * prev
        prev = max(1, int(round(worst * share)))
        if prev > worst:
            raise ValueError(f"{stage} capacity {share} allocates {prev} "
                             f"slots where at most {worst} rows can arrive")
        slots.append(prev)
    return tuple(slots)


def capped_capacities(headroom: DispatchConfig, mesh: EPMesh, n: int,
                      unicast: bool = False) -> DispatchConfig:
    """``headroom`` capped at each stage's no-drop worst case, for ``n``
    rows a chip.  Each stage wants the slots ``headroom``'s share gives
    of the uncapped slots before it, and gets at most the rows that can
    arrive, given the capped slots before it: wherever that wish is
    within the worst case it is kept, and only slots that no routing
    can fill go.  :func:`dispatch_slots` gives the capped slots back for
    ``n`` rows."""
    want = have = n
    shares = []
    for _, fan, share in _stages(headroom, mesh, unicast):
        want = max(1, int(round(fan * want * share)))
        worst = fan * have
        have = min(want, worst)
        shares.append(have / worst)
    fields = (("pod_capacity", "expert_capacity") if unicast else
              ("pod_capacity", "ep_capacity", "expert_capacity"))
    return dataclasses.replace(headroom, **dict(zip(fields, shares)))


def expert_placement(cfg: DispatchConfig, mesh: EPMesh):
    """Experts are placed in contiguous blocks over (pod, ep) ranks."""
    assert cfg.num_experts % mesh.num_ranks == 0, \
        f"{cfg.num_experts} experts over {mesh.num_ranks} EP ranks"
    per_rank = cfg.num_experts // mesh.num_ranks
    return per_rank


def _dest_coords(expert_ids: jax.Array, per_rank: int, ep_per_pod: int):
    """expert id -> (pod, ep) of owning rank."""
    rank = expert_ids // per_rank
    return rank // ep_per_pod, rank % ep_per_pod


def hierarchical_dispatch(tokens: jax.Array, expert_ids: jax.Array,
                          gates: jax.Array, cfg: DispatchConfig,
                          mesh: EPMesh, valid: jax.Array | None = None):
    """MultiWrite MoE dispatch (paper §3.2 / §4).

    Per chip inputs: tokens [N, H]; expert_ids [N, K] i32; gates [N, K] f32;
    valid [N] bool or None (every row): rows that are False (padding) take
    no slot at any stage.

    Stage 1 — ONE copy per (token, destination pod) crosses the pod axis,
    landing on the same-index chip (the rail relay).  The ep-rank bitmap
    (paper §4.1 metadata) travels with the payload.
    Stage 2 — relays parse the bitmap and replicate intra-pod via
    all_to_all over the ep axis (cs_relay).

    Returns (expert_inputs [E_local, Ce, H], DispatchState) where
    DispatchState carries every pack map needed by the combine path.
    """
    n, h = tokens.shape
    k = expert_ids.shape[-1]
    per_rank = expert_placement(cfg, mesh)
    p, d = mesh.num_pods, mesh.ep_per_pod
    pod_of, ep_of = _dest_coords(expert_ids, per_rank, d)   # [N, K] each

    assert per_rank <= 31 and d <= 31 and p <= 31, "bitmap words are int32"

    # ---- stage 1 pack: per destination pod, with ep bitmap metadata -------
    # pod bitmap (which pods does this token need — ONE copy each):
    pod_any = jnp.any(pod_of[..., None] == jnp.arange(p), axis=1)   # [N, P]
    pod_bits = jnp.sum(pod_any.astype(jnp.int32) << jnp.arange(p),
                       axis=-1).astype(jnp.int32)                   # [N]
    # per-pod ep-rank bitmap — the §4.1 in-packet metadata the relay parses:
    ep_onehot = (pod_of[..., None] == jnp.arange(p))[..., None] & \
        (ep_of[..., None] == jnp.arange(d))[:, :, None, :]          # [N,K,P,D]
    ep_any = jnp.any(ep_onehot, axis=1)                             # [N,P,D]
    ep_bits = jnp.sum(
        ep_any.astype(jnp.int32) << jnp.arange(d), axis=-1).astype(jnp.int32)

    cp, cd, ce = dispatch_slots(cfg, mesh, n)
    if valid is None:
        valid = jnp.ones((n,), bool)
    send_tok, map_pod = pack_by_bitmap(tokens, pod_bits, valid, p, cp)
    # metadata rides along (the §4.1 in-packet metadata): ep bitmap for the
    # DESTINATION pod + source row id + (ids, gates) for expert/combine use.
    ep_bits_dst = jnp.stack(
        [gather_rows(ep_bits[:, pp:pp + 1], map_pod[pp])[..., 0]
         for pp in range(p)])                                     # [P, Cp]
    meta_src = jnp.where(map_pod >= 0, map_pod, -1)               # [P, Cp]
    ids_dst = gather_rows(expert_ids, map_pod.reshape(-1)).reshape(p, cp, k)
    gates_dst = gather_rows(gates, map_pod.reshape(-1)).reshape(p, cp, k)

    # ---- stage 1 transport: all_to_all over the pod axis -------------------
    if mesh.pod_axis is not None and p > 1:
        a2a = functools.partial(lax.all_to_all, axis_name=mesh.pod_axis,
                                split_axis=0, concat_axis=0, tiled=True)
        recv_tok = a2a(send_tok.reshape(p * cp, h)).reshape(p, cp, h)
        recv_ep = a2a(ep_bits_dst.reshape(p * cp, 1)).reshape(p, cp)
        recv_src = a2a(meta_src.reshape(p * cp, 1)).reshape(p, cp)
        recv_ids = a2a(ids_dst.reshape(p * cp, k)).reshape(p, cp, k)
        recv_gates = a2a(gates_dst.reshape(p * cp, k)).reshape(p, cp, k)
    else:
        recv_tok, recv_ep = send_tok, ep_bits_dst
        recv_src, recv_ids, recv_gates = meta_src, ids_dst, gates_dst

    # ---- stage 2: relay replication over the ep axis (cs_relay) ------------
    flat_tok = recv_tok.reshape(p * cp, h)
    flat_ep = recv_ep.reshape(p * cp)
    flat_valid = (recv_src.reshape(p * cp) >= 0)
    relay_tok, map_ep = pack_by_bitmap(flat_tok, flat_ep, flat_valid, d, cd)
    relay_ids = gather_rows(recv_ids.reshape(p * cp, k), map_ep.reshape(-1)
                            ).reshape(d, cd, k)
    relay_gates = gather_rows(recv_gates.reshape(p * cp, k),
                              map_ep.reshape(-1)).reshape(d, cd, k)
    if d > 1:
        a2a_ep = functools.partial(lax.all_to_all, axis_name=mesh.ep_axis,
                                   split_axis=0, concat_axis=0, tiled=True)
        got_tok = a2a_ep(relay_tok.reshape(d * cd, h)).reshape(d, cd, h)
        got_ids = a2a_ep(relay_ids.reshape(d * cd, k)).reshape(d, cd, k)
        got_gates = a2a_ep(relay_gates.reshape(d * cd, k)).reshape(d, cd, k)
        got_valid = a2a_ep((map_ep >= 0).reshape(d * cd, 1)).reshape(d, cd)
    else:
        got_tok, got_ids, got_gates = relay_tok, relay_ids, relay_gates
        got_valid = map_ep >= 0

    # ---- stage 3: local per-expert grouping (zero comm) --------------------
    my_pod = lax.axis_index(mesh.pod_axis) if (mesh.pod_axis and p > 1) else 0
    my_ep = lax.axis_index(mesh.ep_axis) if d > 1 else 0
    my_rank = my_pod * d + my_ep
    flat2_tok = got_tok.reshape(d * cd, h)
    flat2_ids = got_ids.reshape(d * cd, k)
    flat2_gates = got_gates.reshape(d * cd, k)
    flat2_valid = got_valid.reshape(d * cd)
    local_e = flat2_ids - my_rank * per_rank                     # [M, K]
    mine = (local_e >= 0) & (local_e < per_rank)
    exp_bits = jnp.sum(
        jnp.where(mine, 1 << jnp.clip(local_e, 0, 30), 0), axis=-1
    ).astype(jnp.int32)
    # OR-safety: top-k ids are distinct -> a token hits each local expert at
    # most once -> sum == OR.  (Routers guarantee distinct ids.)
    exp_tok, map_exp = pack_by_bitmap(flat2_tok, exp_bits, flat2_valid,
                                      per_rank, ce)
    exp_gate = _gate_for_expert(flat2_ids, flat2_gates, map_exp,
                                my_rank * per_rank, per_rank)

    state = DispatchState(map_pod=map_pod, map_ep=map_ep, map_exp=map_exp,
                          recv_src=recv_src, n_tokens=n, cfg=cfg, mesh=mesh)
    return exp_tok, exp_gate, state


def _gate_for_expert(ids: jax.Array, gates: jax.Array, map_exp: jax.Array,
                     base: jax.Array, per_rank: int) -> jax.Array:
    """gate value of each packed (expert, slot) row: the gate of the k-slot
    whose expert id == this expert."""
    e_local, ce = map_exp.shape
    rows_ids = gather_rows(ids, map_exp.reshape(-1)).reshape(e_local, ce, -1)
    rows_gates = gather_rows(gates, map_exp.reshape(-1)
                             ).reshape(e_local, ce, -1)
    want = rows_ids == (base + jnp.arange(e_local))[:, None, None]
    return jnp.sum(jnp.where(want, rows_gates, 0.0), axis=-1)   # [E_l, Ce]


@dataclasses.dataclass
class DispatchState:
    """Pack maps threaded from dispatch to combine (all static-shape)."""
    map_pod: jax.Array    # [P, Cp]  source row per stage-1 slot
    map_ep: jax.Array     # [D, Cd]  stage-1 flat slot per stage-2 slot
    map_exp: jax.Array    # [E_local, Ce] stage-2 flat slot per expert slot
    recv_src: jax.Array   # [P, Cp]  source row id as received (post pod a2a)
    n_tokens: int
    cfg: DispatchConfig
    mesh: EPMesh


jax.tree_util.register_pytree_node(
    DispatchState,
    lambda s: ((s.map_pod, s.map_ep, s.map_exp, s.recv_src),
               (s.n_tokens, s.cfg, s.mesh)),
    lambda aux, ch: DispatchState(*ch, n_tokens=aux[0], cfg=aux[1],
                                  mesh=aux[2]),
)


def hierarchical_combine(expert_out: jax.Array, exp_gate: jax.Array,
                         state: DispatchState) -> jax.Array:
    """Return path with relay-side partial reduction (beyond-paper dual of
    dispatch dedup): per-(token, pod) partials are pre-reduced at the relay
    before crossing the pod axis — ONE partial per (token, pod) on DCN.

    Returns [N, H] combined outputs aligned with the dispatch input rows.
    """
    cfg, mesh = state.cfg, state.mesh
    p, d = mesh.num_pods, mesh.ep_per_pod
    e_local, ce, h = expert_out.shape
    cd = state.map_ep.shape[1]
    cp = state.map_pod.shape[1]

    # ---- apply gates, scatter-add expert slots back to stage-2 slots ------
    weighted = expert_out * exp_gate[..., None]
    flat2 = jnp.zeros((d * cd + 1, h), jnp.float32)
    idx = jnp.where(state.map_exp >= 0, state.map_exp, d * cd)
    flat2 = flat2.at[idx.reshape(-1)].add(
        weighted.reshape(-1, h).astype(jnp.float32))
    flat2 = flat2[:d * cd].reshape(d, cd, h)

    # ---- reverse ep a2a: partials back to the relay ------------------------
    if d > 1:
        back = lax.all_to_all(flat2.reshape(d * cd, h), mesh.ep_axis,
                              split_axis=0, concat_axis=0,
                              tiled=True).reshape(d, cd, h)
    else:
        back = flat2
    # ---- relay-side reduction: sum per stage-1 slot over ep ranks ----------
    flat1 = jnp.zeros((p * cp + 1, h), jnp.float32)
    idxe = jnp.where(state.map_ep >= 0, state.map_ep, p * cp)
    flat1 = flat1.at[idxe.reshape(-1)].add(back.reshape(-1, h))
    flat1 = flat1[:p * cp].reshape(p, cp, h)

    # ---- reverse pod a2a: ONE pre-reduced partial per (token, pod) ---------
    if mesh.pod_axis is not None and p > 1:
        home = lax.all_to_all(flat1.reshape(p * cp, h), mesh.pod_axis,
                              split_axis=0, concat_axis=0,
                              tiled=True).reshape(p, cp, h)
    else:
        home = flat1
    # ---- scatter-add into source rows --------------------------------------
    out = jnp.zeros((state.n_tokens + 1, h), jnp.float32)
    idxp = jnp.where(state.map_pod >= 0, state.map_pod, state.n_tokens)
    out = out.at[idxp.reshape(-1)].add(home.reshape(-1, h))
    return out[:state.n_tokens]


def hierarchical_combine_unicast(expert_out: jax.Array, exp_gate: jax.Array,
                                 state: DispatchState) -> jax.Array:
    """Unicast return path for the hierarchical dispatch: NO relay-side
    reduction — every (token, ep-rank) partial crosses the pod axis
    individually and reduces at the home chip.

    This is the redundant-return baseline the combine planner scores
    against :func:`hierarchical_combine` (one pre-reduced partial per
    (token, pod)): up to ``ep_per_pod`` x more bytes on the slow axis,
    but no relay reduce stage — the Fig 8 trade-off on the return path.
    Numerically equivalent to :func:`hierarchical_combine` (same fp32
    additions, different order).
    """
    mesh = state.mesh
    p, d = mesh.num_pods, mesh.ep_per_pod
    e_local, ce, h = expert_out.shape
    cd = state.map_ep.shape[1]
    cp = state.map_pod.shape[1]

    # ---- apply gates, scatter-add expert slots back to stage-2 slots ------
    weighted = expert_out * exp_gate[..., None]
    flat2 = jnp.zeros((d * cd + 1, h), jnp.float32)
    idx = jnp.where(state.map_exp >= 0, state.map_exp, d * cd)
    flat2 = flat2.at[idx.reshape(-1)].add(
        weighted.reshape(-1, h).astype(jnp.float32))
    flat2 = flat2[:d * cd].reshape(d, cd, h)

    # ---- reverse ep a2a: partials back to the relay ------------------------
    if d > 1:
        back = lax.all_to_all(flat2.reshape(d * cd, h), mesh.ep_axis,
                              split_axis=0, concat_axis=0,
                              tiled=True).reshape(d, cd, h)
    else:
        back = flat2
    # ---- NO relay reduction: one slot per (stage-1 slot, ep rank) ----------
    sl = state.map_ep                                             # [d, cd]
    idx2 = jnp.where(sl >= 0,
                     sl * d + jnp.arange(d, dtype=jnp.int32)[:, None],
                     p * cp * d)
    unred = jnp.zeros((p * cp * d + 1, h), jnp.float32)
    unred = unred.at[idx2.reshape(-1)].add(back.reshape(-1, h))
    unred = unred[:p * cp * d].reshape(p, cp * d, h)

    # ---- reverse pod a2a: d unreduced partials per stage-1 slot ------------
    if mesh.pod_axis is not None and p > 1:
        home = lax.all_to_all(unred.reshape(p * cp * d, h), mesh.pod_axis,
                              split_axis=0, concat_axis=0,
                              tiled=True).reshape(p, cp * d, h)
    else:
        home = unred
    # ---- reduce AFTER crossing, scatter-add into source rows ---------------
    home = home.reshape(p, cp, d, h).sum(axis=2)
    out = jnp.zeros((state.n_tokens + 1, h), jnp.float32)
    idxp = jnp.where(state.map_pod >= 0, state.map_pod, state.n_tokens)
    out = out.at[idxp.reshape(-1)].add(home.reshape(-1, h))
    return out[:state.n_tokens]


# ===========================================================================
# Baseline (unicast) dispatch / combine — one copy per (token, dest chip)
# ===========================================================================

def baseline_dispatch(tokens: jax.Array, expert_ids: jax.Array,
                      gates: jax.Array, cfg: DispatchConfig, mesh: EPMesh,
                      valid: jax.Array | None = None):
    """Unicast dispatch: pack one copy per (token, destination RANK) and
    all_to_all over the flattened (pod, ep) domain — k_remote redundant
    copies of each token cross the pod axis (the paper's baseline).
    ``valid`` as in :func:`hierarchical_dispatch`."""
    n, h = tokens.shape
    k = expert_ids.shape[-1]
    per_rank = expert_placement(cfg, mesh)
    p, d = mesh.num_pods, mesh.ep_per_pod
    r = p * d
    rank_of = (expert_ids // per_rank).astype(jnp.int32)          # [N, K]
    rank_any = jnp.any(rank_of[..., None] == jnp.arange(r), axis=1)  # [N, R]
    rank_bits32 = [jnp.sum(rank_any[:, w * 31:(w + 1) * 31].astype(jnp.int32)
                           << jnp.arange(min(31, r - w * 31)), axis=-1)
                   for w in range((r + 30) // 31)]
    cr, ce = dispatch_slots(cfg, mesh, n, unicast=True)
    if valid is None:
        valid = jnp.ones((n,), bool)
    # pack per rank using multi-word bitmaps
    outs, maps = [], []
    for w, bits in enumerate(rank_bits32):
        nd = min(31, r - w * 31)
        o, m = pack_by_bitmap(tokens, bits, valid, nd, cr)
        outs.append(o)
        maps.append(m)
    send_tok = jnp.concatenate(outs, axis=0)                      # [R, Cr, H]
    map_rank = jnp.concatenate(maps, axis=0)                      # [R, Cr]
    ids_send = gather_rows(expert_ids, map_rank.reshape(-1)).reshape(r, cr, k)
    gates_send = gather_rows(gates, map_rank.reshape(-1)).reshape(r, cr, k)

    # transport: a2a over ep then pod (equivalent to flattened-domain a2a)
    def a2a_both(x):
        x = x.reshape(p, d, cr, -1)
        if d > 1:
            x = lax.all_to_all(x, mesh.ep_axis, split_axis=1, concat_axis=1,
                               tiled=True)
        if mesh.pod_axis is not None and p > 1:
            x = lax.all_to_all(x, mesh.pod_axis, split_axis=0, concat_axis=0,
                               tiled=True)
        return x.reshape(r, cr, -1)

    got_tok = a2a_both(send_tok)
    got_ids = a2a_both(ids_send).astype(jnp.int32)
    got_gates = a2a_both(gates_send)
    got_valid = a2a_both((map_rank >= 0).astype(jnp.int32)[..., None]
                         )[..., 0] > 0

    my_pod = lax.axis_index(mesh.pod_axis) if (mesh.pod_axis and p > 1) else 0
    my_ep = lax.axis_index(mesh.ep_axis) if d > 1 else 0
    my_rank = my_pod * d + my_ep
    flat_tok = got_tok.reshape(r * cr, h)
    flat_ids = got_ids.reshape(r * cr, k)
    flat_gates = got_gates.reshape(r * cr, k)
    local_e = flat_ids - my_rank * per_rank
    mine = (local_e >= 0) & (local_e < per_rank)
    exp_bits = jnp.sum(jnp.where(mine, 1 << jnp.clip(local_e, 0, 30), 0),
                       axis=-1).astype(jnp.int32)
    exp_tok, map_exp = pack_by_bitmap(flat_tok, exp_bits,
                                      got_valid.reshape(r * cr), per_rank, ce)
    exp_gate = _gate_for_expert(flat_ids, flat_gates, map_exp,
                                my_rank * per_rank, per_rank)
    state = BaselineState(map_rank=map_rank, map_exp=map_exp, n_tokens=n,
                          cfg=cfg, mesh=mesh)
    return exp_tok, exp_gate, state


@dataclasses.dataclass
class BaselineState:
    map_rank: jax.Array   # [R, Cr]
    map_exp: jax.Array    # [E_local, Ce]
    n_tokens: int
    cfg: DispatchConfig
    mesh: EPMesh


jax.tree_util.register_pytree_node(
    BaselineState,
    lambda s: ((s.map_rank, s.map_exp), (s.n_tokens, s.cfg, s.mesh)),
    lambda aux, ch: BaselineState(*ch, n_tokens=aux[0], cfg=aux[1],
                                  mesh=aux[2]),
)


def baseline_combine(expert_out: jax.Array, exp_gate: jax.Array,
                     state: BaselineState) -> jax.Array:
    """Unicast combine: per-(token, expert-rank) outputs return individually
    over both axes (no relay reduction) and are summed at the source."""
    cfg, mesh = state.cfg, state.mesh
    p, d = mesh.num_pods, mesh.ep_per_pod
    r = p * d
    e_local, ce, h = expert_out.shape
    cr = state.map_rank.shape[1]
    weighted = expert_out * exp_gate[..., None]
    flat = jnp.zeros((r * cr + 1, h), jnp.float32)
    idx = jnp.where(state.map_exp >= 0, state.map_exp, r * cr)
    flat = flat.at[idx.reshape(-1)].add(
        weighted.reshape(-1, h).astype(jnp.float32))
    flat = flat[:r * cr]

    def a2a_both_back(x):
        x = x.reshape(p, d, cr, -1)
        if mesh.pod_axis is not None and p > 1:
            x = lax.all_to_all(x, mesh.pod_axis, split_axis=0, concat_axis=0,
                               tiled=True)
        if d > 1:
            x = lax.all_to_all(x, mesh.ep_axis, split_axis=1, concat_axis=1,
                               tiled=True)
        return x.reshape(r, cr, -1)

    home = a2a_both_back(flat)
    out = jnp.zeros((state.n_tokens + 1, h), jnp.float32)
    idxr = jnp.where(state.map_rank >= 0, state.map_rank, state.n_tokens)
    out = out.at[idxr.reshape(-1)].add(home.reshape(-1, h))
    return out[:state.n_tokens]


# ===========================================================================
# Planned gradient sync: AllReduce as a planner op (shard_map lowerings)
# ===========================================================================

def butterfly_psum(g: jax.Array, axis_name: str) -> jax.Array:
    """Recursive-doubling tree AllReduce: log2(R) ppermute rounds, each
    exchanging the full payload with the XOR partner and reducing — the
    latency-optimal endpoint of the reduce scheme family (the ledger the
    planner scores as the ``tree`` plan).  Returns the SUM over the
    axis.  Requires a power-of-two axis; must run inside shard_map."""
    n = axis_size(axis_name)
    if n & (n - 1):
        raise ValueError(f"butterfly_psum needs a power-of-two axis "
                         f"(got {n})")
    out = g
    k = 1
    while k < n:
        perm = [(i, i ^ k) for i in range(n)]
        out = out + lax.ppermute(out, axis_name, perm)
        k <<= 1
    return out


def planned_psum(g: jax.Array, axis_name: str, *, num_servers: int = 1,
                 decision=None, reduce_scheme: str = None,
                 planner=None, hw=None, compute_s: float = 0.0) -> jax.Array:
    """Gradient MEAN-reduce over ``axis_name`` whose schedule comes from
    a planner decision instead of a hard-coded ``lax.psum``.

    ``decision`` is the ``grad_sync`` verdict of a bound
    :class:`~repro.core.plan.ExecutionPlan`; ``reduce_scheme`` pins a
    scheme directly (tests / operational override).  Without either, the
    process planner decides here from the payload and the DP fabric
    (``num_servers`` server groups of the axis, fabric order).  Must be
    called inside ``shard_map`` with ``axis_name`` bound.

    Scheme -> lowering:
      ring          ``lax.psum`` (XLA's own flat ring — the baseline)
      tree          :func:`butterfly_psum` XOR-partner rounds
      hierarchical  ``hierarchical_psum_flat`` (RS -> rail exchange -> AG)
      multiwrite    ``hierarchical_psum_flat`` — on TPU the relay-reduce
                    schedule lowers to the same RS/exchange/AG structure
                    (the ledger difference is the relay engine accounting)
      compressed    int8 error-feedback ``compressed_psum`` (LOSSY —
                    never planner-chosen, explicit opt-in only)

    All lossless schemes are numerically equivalent to
    ``lax.psum(g) / R`` up to float summation order.
    """
    import math as _math

    from repro.core import planner as _planner_mod

    scheme = reduce_scheme
    if scheme is None:
        if decision is None:
            n = axis_size(axis_name)
            payload = _math.prod(g.shape) * g.dtype.itemsize
            pl = planner or _planner_mod.default_planner()
            topo = _planner_mod._ep_topology(
                max(1, num_servers), max(1, n // max(1, num_servers)))
            decision = pl.choose("allreduce", payload, topo, hw,
                                 executable_only=True, compute_s=compute_s)
        scheme = decision.shard_map_kwargs.get("reduce_scheme", "ring")
    r = axis_size(axis_name)
    if scheme == "ring":
        return lax.psum(g, axis_name) / r
    if scheme == "tree":
        if r & (r - 1):
            return lax.psum(g, axis_name) / r     # non-pow2: ring fallback
        return butterfly_psum(g, axis_name) / r
    if scheme in ("hierarchical", "multiwrite"):
        from repro.parallel.compression import hierarchical_psum_flat
        s = max(1, num_servers)
        if r % s:
            return lax.psum(g, axis_name) / r     # unfactorable: fallback
        out = hierarchical_psum_flat(g.reshape(-1), axis_name, s)
        return out.reshape(g.shape).astype(g.dtype)
    if scheme == "compressed":
        from repro.parallel.compression import compressed_psum
        out, _ = compressed_psum(g.reshape(-1), axis_name)
        return out.reshape(g.shape).astype(g.dtype)
    raise ValueError(f"unknown reduce scheme {scheme!r}")


# ===========================================================================
# Analytic pod-axis byte accounting (feeds the paper-validation benches)
# ===========================================================================

def dispatch_pod_bytes(expert_ids, cfg: DispatchConfig, mesh: EPMesh,
                       h: int, elem_bytes: int = 2):
    """(baseline_bytes, multiwrite_bytes) crossing the pod axis per chip —
    the Table-1 quantity at pod scale.  expert_ids: [N, K] (numpy ok)."""
    import numpy as np
    ids = np.asarray(expert_ids)
    per_rank = cfg.num_experts // mesh.num_ranks
    rank = ids // per_rank
    pod = rank // mesh.ep_per_pod
    # chips/pods distinct per token, restricted to REMOTE pods
    base = mw = 0
    for row_rank, row_pod in zip(rank, pod):
        # assume source pod 0 (symmetric under balance)
        remote = row_pod != 0
        base += len(set(row_rank[remote]))
        mw += len(set(row_pod[remote]))
    return base * h * elem_bytes, mw * h * elem_bytes
