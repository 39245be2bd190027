"""Plain reference of the switch-less fabric under minimal routing.

It re-implements, in straightforward numpy and from the fabric's published
description (arXiv:2407.10290, Sec. III-IV), one lane of the flit-level
simulation that a benchmark cell times:

- the switch-less Dragonfly graph: C-groups of R x R routers joined by a
  2-D mesh, C-groups of a W-group fully connected by local links, W-groups
  fully connected by global links, one terminal per router;
- minimal routing (Alg. 1: to the C-group that owns the global link, over
  it, then to the destination C-group; XY order inside a C-group) with the
  baseline VC scheme (VC class = C-groups entered so far), `vcs_per_class`
  physical VCs per class, the least occupied one taken;
- virtual cut-through with credit flow control, `buf_pkts`-packet input
  buffers, `srcq_pkts`-packet source queues, oldest-first arbitration per
  output channel (lowest request row on ties), a channel busy for
  ceil(pkt_len / bandwidth) cycles after each grant;
- statistics zeroed once, after the warm-up's last cycle, except the
  high-water mark of live request rows.

It imports nothing of the program and takes none of its tables.  What it
shares with the program is only the input: the traffic each lane offers is
drawn with `jax.random` from the lane's seed (`draws`), cycle by cycle, as
the cell's traffic defines it.

`IMPLEMENTS` declares the cells it covers (`bench/check.py` finds it by
that), and `replay` is its entry point.  `Lane.step` is the whole cycle.
`stats_dtype` lets the control of the correctness check keep the
statistics one precision lower.
"""
from __future__ import annotations

import numpy as np

MESH, LOCAL, GLOBAL, INJECT, EJECT = range(5)
NUM_TYPES = 5
DIRS = ((0, -1), (1, 0), (0, 1), (-1, 0))      # N, E, S, W as (dx, dy)
COUNTERS = ("generated", "delivered", "dropped", "stranded", "reaped",
            "occ_peak")

# the cells this reference replays: each dotted path into the cell's
# {"config": ..., "traffic": ...} and the values it implements there (None:
# the path is absent, as on a pristine fabric with no fault set)
IMPLEMENTS = {"config.topology.kind": ("switchless",),
              "traffic.pattern": ("uniform",),
              "config.routing.route_mode": ("min",),
              "config.routing.vc_mode": ("baseline",),
              "config.faults": (None,),
              "traffic.faults": (None,)}


def perimeter(R: int) -> list:
    """Clockwise walk of an R x R grid's edge from (0, 0): the polar port
    labels of the paper's Fig. 8(c)."""
    if R == 1:
        return [(0, 0)]
    return ([(x, 0) for x in range(R - 1)]
            + [(R - 1, y) for y in range(R - 1)]
            + [(x, R - 1) for x in range(R - 1, 0, -1)]
            + [(0, y) for y in range(R - 1, 0, -1)])


class Fabric:
    """The switch-less Dragonfly graph and its minimal routes.

    Node ids run W-group, C-group, row, column; channel ids run mesh
    (C-group, row, column, N/E/S/W), injection (terminal), local (W-group,
    C-group, peer), global (W-group, peer, parallel link), ejection
    (terminal).  Request rows run (channel, VC) for every channel but the
    ejections, then one per source queue; their order breaks age ties.
    """

    def __init__(self, a, b, m, n, noc=2, g=None, cg_bw_mult=1,
                 lr_latency=8, sr_latency=1, chip_routers=None,
                 pkt_len=4):
        ab, k = a * b, n * m
        h = k - ab + 1
        g = g or ab * h + 1
        if h < 1 or not 1 <= g <= ab * h + 1:
            raise ValueError(f"no switch-less fabric with ab={ab}, k={k}, "
                             f"g={g}")
        R = m * noc
        npc = R * R
        ncg = ab * g
        V = ncg * npc
        self.ab, self.h, self.g, self.R, self.npc, self.V = ab, h, g, R, npc, V
        self.T = V
        self.chips = V // (chip_routers or noc * noc)

        walk = perimeter(R)
        port_xy = [walk[i] for i in (np.arange(k) * len(walk)) // k]
        self.port_local = np.array([y * R + x for x, y in port_xy])
        local_port = np.full((ab, ab), -1, dtype=np.int64)
        for c in range(ab):
            for p in range(ab):
                if p != c:
                    local_port[c, p] = p if p < c else h + p - 1
        self.local_port = local_port

        src, dst, typ, bw, lat = [], [], [], [], []

        def add(s, d, ty, b_, l_):
            src.append(s); dst.append(d); typ.append(ty)
            bw.append(b_); lat.append(l_)
            return len(src) - 1

        self.mesh_ch = np.full((V, 4), -1, dtype=np.int64)
        for cgg in range(ncg):
            for y in range(R):
                for x in range(R):
                    s = cgg * npc + y * R + x
                    for di, (dx, dy) in enumerate(DIRS):
                        if 0 <= x + dx < R and 0 <= y + dy < R:
                            self.mesh_ch[s, di] = add(
                                s, cgg * npc + (y + dy) * R + x + dx, MESH,
                                cg_bw_mult, sr_latency)
        self.inject_ch = np.array([add(V + t, t, INJECT, 1, 1)
                                   for t in range(V)])
        self.ext_out = np.full((ncg, k), -1, dtype=np.int64)
        for w in range(g):
            for c1 in range(ab):
                for c2 in range(ab):
                    if c1 != c2:
                        p1, p2 = local_port[c1, c2], local_port[c2, c1]
                        self.ext_out[w * ab + c1, p1] = add(
                            (w * ab + c1) * npc + self.port_local[p1],
                            (w * ab + c2) * npc + self.port_local[p2],
                            LOCAL, 1, lr_latency)
        # global port q = cg * h + j of W-group w (label cg + j) leads to
        # W-group (w + q + 1) mod g; surplus ports add parallel links
        npar = max(1, (ab * h) // max(g - 1, 1)) if g > 1 else 1
        gcg = np.full((g, g, npar), -1, dtype=np.int64)
        gport = np.full((g, g, npar), -1, dtype=np.int64)
        for w in range(g if g > 1 else 0):
            cnt = np.zeros(g, dtype=np.int64)
            for q in range(ab * h):
                u = (w + q + 1) % g
                if u == w or cnt[u] >= npar:
                    continue
                c, j = divmod(q, h)
                gcg[w, u, cnt[u]], gport[w, u, cnt[u]] = c, c + j
                cnt[u] += 1
        # a flow leaving w for u takes the (dest mod count)-th link wired
        # both ways
        self.exit_cnt = np.ones((g, g), dtype=np.int64)
        self.exit_cg = np.full((g, g, npar), -1, dtype=np.int64)
        self.exit_port = np.full((g, g, npar), -1, dtype=np.int64)
        for w in range(g):
            for u in range(g):
                if u == w:
                    continue
                wired = [r for r in range(npar)
                         if gcg[w, u, r] >= 0 and gcg[u, w, r] >= 0]
                for i, r in enumerate(wired):
                    self.ext_out[w * ab + gcg[w, u, r], gport[w, u, r]] = add(
                        (w * ab + gcg[w, u, r]) * npc
                        + self.port_local[gport[w, u, r]],
                        (u * ab + gcg[u, w, r]) * npc
                        + self.port_local[gport[u, w, r]],
                        GLOBAL, 1, lr_latency)
                    self.exit_cg[w, u, i] = gcg[w, u, r]
                    self.exit_port[w, u, i] = gport[w, u, r]
                self.exit_cnt[w, u] = max(len(wired), 1)
        self.first_eject = len(src)
        self.eject_ch = np.array([add(t, V + t, EJECT, 1, 1)
                                  for t in range(V)])

        self.ch_dst = np.array(dst)
        self.ch_type = np.array(typ)
        self.ch_lat = np.array(lat)
        self.ser = (pkt_len + np.array(bw) - 1) // np.array(bw)
        self.E = len(src)
        node = np.arange(V)
        self.node_cgg = node // npc
        self.node_wg = self.node_cgg // ab

    def route(self, cur, dest, meta):
        """Minimal route of packets at router `cur` bound for terminal
        `dest`: (output channel, VC class, routing meta after the hop).
        `meta` packs C-groups entered (bits 0-2), global hops (bits 3-4)
        and whether the packet entered its C-group over a link (bit 5)."""
        npc, R, ab = self.npc, self.R, self.ab
        cgg_c, cgg_d = self.node_cgg[cur], self.node_cgg[dest]
        wg_c, wg_d = cgg_c // ab, cgg_d // ab
        cg_c, cg_d = cgg_c % ab, cgg_d % ab
        at_dest_cg = cgg_c == cgg_d
        in_dest_wg = wg_c == wg_d
        slot = dest % self.exit_cnt[wg_c, wg_d]
        cg_gl = self.exit_cg[wg_c, wg_d, slot]
        use_global = ~in_dest_wg & (cg_c == cg_gl)
        peer = np.where(in_dest_wg, cg_d, cg_gl)
        port = np.where(use_global, self.exit_port[wg_c, wg_d, slot],
                        self.local_port[cg_c, peer])
        # the router to reach inside this C-group: the destination's, or
        # the one holding the exit port (index -1 only where unused)
        tgt = np.where(at_dest_cg, dest % npc, self.port_local[port])
        here = cur % npc
        x, y, tx, ty = here % R, here // R, tgt % R, tgt // R
        d = np.where(x != tx, np.where(tx > x, 1, 3),
                     np.where(ty > y, 2, 0))
        out = np.where(here == tgt,
                       np.where(at_dest_cg, self.eject_ch[cur],
                                self.ext_out[cgg_c, port]),
                       self.mesh_ch[cur, d])
        if (out < 0).any():
            raise AssertionError("a minimal route left the fabric")
        ty_ = self.ch_type[out]
        ext = (ty_ == LOCAL) | (ty_ == GLOBAL)
        cgs = np.minimum((meta & 7) + ext, 7)
        gls = np.minimum(((meta >> 3) & 3) + (ty_ == GLOBAL), 3)
        via = np.where(ty_ == MESH, (meta >> 5) & 1, ext)
        meta2 = (cgs | (gls << 3) | (via << 5)).astype(np.int32)
        return out, np.where(ty_ == EJECT, 0, cgs), meta2


def draws(seed: int, cycles: int, terminals: int):
    """The traffic one lane offers in its first `cycles` cycles: for cycle
    t, a uniform variate per terminal (it injects when the variate is
    under its packet rate) and a destination uniform over the other
    terminals.  The lane key chain: key_{t+1}, sub_t = split(key_t), and
    sub_t splits into (generation, destination, misroute) keys.
    Computed on the host CPU."""
    import jax
    import jax.numpy as jnp

    cpu = jax.devices("cpu")[0]

    @jax.jit
    def chain(key):
        def body(k, _):
            k, sub = jax.random.split(k)
            k_gen, k_dest, _k_mis = jax.random.split(sub, 3)
            u = jax.random.uniform(k_gen, (terminals,))
            d = jax.random.randint(k_dest, (terminals,), 0, terminals - 1)
            return k, (u, jnp.where(d >= jnp.arange(terminals), d + 1, d))
        return jax.lax.scan(body, key, None, length=cycles)[1]

    with jax.default_device(cpu):
        u, d = chain(jax.random.PRNGKey(seed))
        return np.asarray(u), np.asarray(d)


class Lane:
    """One lane's state: ring buffers per (channel, VC), source queues,
    channel busy counts and the statistics."""

    def __init__(self, fab: Fabric, *, vcs_per_class=2, classes=4,
                 buf_pkts=8, srcq_pkts=64, rate=0.0, warmup=0,
                 stats_dtype=(np.int32, np.float32)):
        self.f = fab
        self.vpc, self.NV = vcs_per_class, classes * vcs_per_class
        self.S, self.Q = buf_pkts, srcq_pkts
        self.rate = np.float32(rate)
        self.warmup = warmup
        self.int_t, self.float_t = stats_dtype
        E, NV, S, T = fab.E, self.NV, self.S, fab.T
        z = lambda *s: np.zeros(s, dtype=np.int32)
        self.b_dest, self.b_itime = z(E, NV, S), z(E, NV, S)
        self.b_meta, self.b_ready = z(E, NV, S), z(E, NV, S)
        self.b_head, self.b_count = z(E, NV), z(E, NV)
        self.s_dest, self.s_itime = z(T, self.Q), z(T, self.Q)
        self.s_head, self.s_count = z(T), z(T)
        self.ch_busy = z(E)
        self.occ_peak = 0
        self.zero_stats()

    def zero_stats(self):
        it = self.int_t
        for name in COUNTERS[:-1]:
            setattr(self, name, it(0))
        self.hops = np.zeros(NUM_TYPES, dtype=it)
        self.lat_sum = self.float_t(0)

    def _add(self, name, n):
        # wraps like a fixed-width counter of the statistics' type
        setattr(self, name, (getattr(self, name) + np.int64(n)).astype(
            self.int_t))

    def step(self, t: int, u, dest):
        f, NV, S, Q = self.f, self.NV, self.S, self.Q
        ER = f.first_eject
        # injection: a packet per terminal whose variate is under the rate,
        # dropped when its source queue is full
        gen = u < self.rate
        space = self.s_count < Q
        tt = np.flatnonzero(gen & space)
        slot = (self.s_head[tt] + self.s_count[tt]) % Q
        self.s_dest[tt, slot] = dest[tt]
        self.s_itime[tt, slot] = t
        self.s_count[tt] += 1
        self._add("generated", gen.sum())
        self._add("dropped", (gen & ~space).sum())
        self.occ_peak = max(self.occ_peak, int(np.count_nonzero(self.b_count)
                                               + np.count_nonzero(self.s_count)))

        # requests: ready heads of non-empty buffers, then source queues
        rows = np.flatnonzero(self.b_count[:ER].ravel())
        e, v = rows // NV, rows % NV
        hd = self.b_head[e, v]
        ready = self.b_ready[e, v, hd] <= t
        rows, e, v, hd = rows[ready], e[ready], v[ready], hd[ready]
        out_b, cls_b, meta_b = f.route(f.ch_dst[e], self.b_dest[e, v, hd],
                                       self.b_meta[e, v, hd])
        ts = np.flatnonzero(self.s_count)
        sh = self.s_head[ts]
        nb = len(rows)
        ridx = np.concatenate([rows, ER * NV + ts])
        out = np.concatenate([out_b, f.inject_ch[ts]])
        cls = np.concatenate([cls_b, np.zeros(len(ts), dtype=np.int64)])
        meta = np.concatenate([meta_b, np.zeros(len(ts), dtype=np.int32)])
        dst = np.concatenate([self.b_dest[e, v, hd], self.s_dest[ts, sh]])
        itime = np.concatenate([self.b_itime[e, v, hd],
                                self.s_itime[ts, sh]])
        if (cls >= NV // self.vpc).any():
            raise AssertionError("VC class beyond the configured classes")

        # least occupied VC of the class, the first on ties
        vcs = cls[:, None] * self.vpc + np.arange(self.vpc)
        occ = self.b_count[out[:, None], vcs]
        pick = occ.argmin(1)
        vc = vcs[np.arange(len(out)), pick]
        ovc = occ[np.arange(len(out)), pick]
        otype = f.ch_type[out]
        is_ej = otype == EJECT
        ok = np.flatnonzero((self.ch_busy[out] == 0) & ((ovc < S) | is_ej))

        # one grant per output channel: oldest first, lowest row on ties
        order = ok[np.lexsort((ridx[ok], itime[ok], out[ok]))]
        first = np.ones(len(order), dtype=bool)
        first[1:] = out[order][1:] != out[order][:-1]
        win = order[first]

        ej = win[is_ej[win]]
        self._add("delivered", len(ej))
        lat = np.int64(t) * len(ej) - itime[ej].astype(np.int64).sum()
        self.lat_sum = (self.lat_sum + self.float_t(lat)).astype(self.float_t)
        self.hops = (self.hops + np.bincount(otype[win], minlength=NUM_TYPES)
                     ).astype(self.int_t)
        self.stranded = self.int_t(0)

        # moves: the tail slot of each target buffer before this cycle's pops
        push = win[~is_ej[win]]
        po, pv = out[push], vc[push]
        pslot = (self.b_head[po, pv] + ovc[push]) % S
        wb = win[win < nb]
        self.b_head[e[wb], v[wb]] = (self.b_head[e[wb], v[wb]] + 1) % S
        self.b_count[e[wb], v[wb]] -= 1
        ws = ts[win[win >= nb] - nb]
        self.s_head[ws] = (self.s_head[ws] + 1) % Q
        self.s_count[ws] -= 1
        self.b_dest[po, pv, pslot] = dst[push]
        self.b_itime[po, pv, pslot] = itime[push]
        self.b_meta[po, pv, pslot] = meta[push]
        self.b_ready[po, pv, pslot] = t + f.ch_lat[po]
        self.b_count[po, pv] += 1
        busy = np.maximum(self.ch_busy - 1, 0)
        busy[out[win]] = f.ser[out[win]] - 1
        self.ch_busy = busy.astype(np.int32)
        if t == self.warmup:
            self.zero_stats()

    def record(self) -> dict:
        """The lane's statistics and in-flight state, as compared."""
        rec = {name: int(getattr(self, name)) for name in COUNTERS}
        rec["hops"] = np.asarray(self.hops, dtype=np.int64)
        rec["lat_sum"] = float(self.lat_sum)
        rec["b_count"] = self.b_count.copy()
        rec["s_count"] = self.s_count.copy()
        rec["ch_busy"] = self.ch_busy.copy()
        return rec



def lane_rate(config: dict, offered: float, fab: Fabric) -> float:
    """Per-terminal packet rate of `offered` flits/cycle/chip, as the
    float32 the lanes compare their variates with."""
    pkt_len = config["routing"]["pkt_len"]
    return float(np.float32(offered / pkt_len / (fab.T / fab.chips)))


def replay(config: dict, traffic: dict, lanes, cycles: int,
           stats_dtype) -> list:
    """Each lane, given as (offered flits/cycle/chip, seed), replayed from
    an empty fabric to `cycles`, with its statistics in `stats_dtype`
    (integer type, float type); one `Lane.record()` per lane."""
    routing = config["routing"]
    fab = Fabric(**config["topology"]["params"], pkt_len=routing["pkt_len"])
    out = []
    for offered, seed in lanes:
        u, d = draws(seed, cycles, fab.T)
        lane = Lane(fab, vcs_per_class=routing["vcs_per_class"],
                    classes=config["vc_classes"],
                    buf_pkts=routing["buf_pkts"],
                    srcq_pkts=routing["srcq_pkts"],
                    rate=lane_rate(config, offered, fab),
                    warmup=traffic["fill"], stats_dtype=stats_dtype)
        for t in range(cycles):
            lane.step(t, u[t], d[t])
        out.append(lane.record())
    return out
