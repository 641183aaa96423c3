"""Plain reference of the policy engine and of its online learner.

What the program computes for one job stream, one policy grid and S spot
markets (arXiv 2106.01847: Alg. 1 windows, policy (12) self-owned counts,
Def. 3.2 spot/on-demand realization with early starts, Alg. 4's learner
over a shared pool), written here once more in straightforward numpy and
imported from nowhere in the program:

* ``chain``      — the DAG -> chain pseudo-schedule transform (App. B.1);
* ``windows``    — Dealloc's greedy slack waterfill (Alg. 1);
* ``prices``     — the "fresh" market family: a stateless counter hash of
                   (seed, scenario index, slot) into 24-bit levels, then the
                   shifted-exponential price law, clipped at the ceiling;
* ``unit_costs`` — per-bid cumulative availability / payment integrals and
                   the closed-form chain realization: a task rides spot
                   while it has flexibility and turns to on-demand at the
                   first instant it has none;
* ``tola``       — Alg. 4 with one pool refinement: exponentiated weights
                   (``hedge``) draw each job's policy, the draws run against
                   one shared pool in order of planned start (``allocate``,
                   ``realize``), and the grid is scored again against what
                   that run left free (``residual_query``).

Every function takes the float dtype to compute in: float64 is the
reference, a narrower type (``ml_dtypes.bfloat16``) is the control that the
comparison must refuse.
"""

from __future__ import annotations

import numpy as np

__all__ = ["chain", "windows", "prices", "unit_costs", "policy_grid",
           "compare", "hedge", "allocate", "realize", "tola", "compare_tola"]

# Thresholds of the paper's model, as the program states them.
_EPS = 1e-12           # zero-length pseudo-schedule intervals
_WORK_EPS = 1e-15      # "any cloud work left"
_FLEX_REL = 1e-4       # no flexibility: slack <= max(REL * window, ABS * end)
_FLEX_ABS = 1e-5
_DUST = 1e-9           # z - r * size residue of fully self-owned tasks
_M32 = 0xFFFFFFFF


# -- jobs -------------------------------------------------------------------

def chain(arrival: float, deadline: float, z, delta, preds):
    """Chain pseudo-job of one DAG: (arrival, deadline, z, delta) arrays.

    Every task runs at its full parallelism as early as its predecessors
    allow; each interval between consecutive start / finish events becomes
    one chain task whose parallelism is the sum of the tasks running in it.
    """
    z = np.asarray(z, np.float64)
    delta = np.asarray(delta, np.float64)
    e = z / delta
    q = np.zeros(len(e))
    for i, ps in enumerate(preds):
        if ps:
            q[i] = max(q[p] + e[p] for p in ps)
    ev = np.unique(np.concatenate([q, q + e]))
    ev = ev[np.concatenate([[True], np.diff(ev) > _EPS])]
    cz, cd = [], []
    for lo, hi in zip(ev[:-1], ev[1:]):
        load = float(delta[(q < hi - _EPS) & (q + e > lo + _EPS)].sum())
        if hi - lo > _EPS and load > _EPS:
            cz.append(load * (hi - lo))
            cd.append(load)
    if not cz:
        cz, cd = [0.0], [1.0]
    return arrival, deadline, np.array(cz), np.array(cd)


def pad(chains):
    """Stack chains into (J,) arrivals / deadlines and (J, L) z, delta."""
    J = len(chains)
    L = max(len(c[2]) for c in chains)
    arr = np.array([c[0] for c in chains])
    dl = np.array([c[1] for c in chains])
    z = np.zeros((J, L))
    d = np.ones((J, L))
    m = np.zeros((J, L), bool)
    for j, (_, _, cz, cd) in enumerate(chains):
        z[j, :len(cz)] = cz
        d[j, :len(cd)] = cd
        m[j, :len(cz)] = True
    return arr, dl, z, d, m


def windows(z, d, mask, arrival, deadline, x: float):
    """(J, L) planned task deadlines under Dealloc(x).

    Each task starts with its minimum time e; the slack goes, in order of
    non-increasing parallelism, to each task up to e / x - e, and whatever
    is left to the task of largest parallelism.
    """
    J, L = z.shape
    e = np.where(mask, z / d, 0.0)
    ends = np.empty((J, L))
    for j in range(J):
        n = int(mask[j].sum())
        ej = e[j, :n]
        sizes = ej.copy()
        omega = max((deadline[j] - arrival[j]) - float(ej.sum()), 0.0)
        order = np.argsort(-d[j, :n], kind="stable")
        cap = ej / x - ej
        for i in order:
            if omega <= 0.0:
                break
            give = min(cap[i], omega)
            sizes[i] += give
            omega -= give
        if omega > 0.0:
            sizes[order[0]] += omega
        ends[j, :n] = arrival[j] + np.cumsum(sizes)
        ends[j, n:] = ends[j, n - 1]
    return ends


def selfowned(z, d, mask, starts, ends, beta0, avail):
    """Policy (12): (J, L) self-owned counts r on windows [starts, ends).

    ``beta0`` is a scalar or a (J, 1) column; ``avail`` the instances free
    over each window: ``r_total`` for a dedicated pool, or a (J, L) array.
    """
    size = np.maximum(ends - starts, 1e-12)
    b0 = np.broadcast_to(np.asarray(beta0, np.float64), z.shape)
    one = b0 >= 1.0 - 1e-12
    f = (z - d * size * b0) / (size * np.where(one, 1.0, 1.0 - b0))
    f = np.where(one, 0.0, np.maximum(f, 0.0))
    f = np.ceil(f - 1e-9)
    useful = np.ceil(np.where(ends - starts > 0, z / size, 0.0) - 1e-9)
    r = np.maximum(0.0, np.minimum(np.minimum(f, avail),
                                   np.minimum(d, useful)))
    return np.where(mask, r, 0.0)


def residuals(z, d, starts, ends, r):
    """Cloud work z_t (dust removed), cloud parallelism and the pins of a
    plan whose tasks hold r self-owned instances."""
    z_t = np.maximum(z - r * (ends - starts), 0.0)
    z_t[z_t <= _DUST * (z + 1.0)] = 0.0
    return z_t, np.maximum(d - r, 0.0), r > 0


# -- markets ----------------------------------------------------------------

def _mix(x):
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    x = x ^ (x >> np.uint32(16))
    return x


def _mix_int(x: int) -> int:
    x &= _M32
    x ^= x >> 16
    x = (x * 0x7FEB352D) & _M32
    x ^= x >> 15
    x = (x * 0x846CA68B) & _M32
    x ^= x >> 16
    return x


def prices(market: dict, idx, n_slots: int):
    """(len(idx), n_slots) float64 per-slot prices of the fresh family."""
    base = np.uint32(_mix_int((market["seed"] & _M32)))
    with np.errstate(over="ignore"):
        row = _mix(np.asarray(idx).astype(np.uint32) * np.uint32(0x9E3779B9)
                   ^ base)
        col = np.arange(n_slots, dtype=np.uint32) * np.uint32(0x85EBCA6B)
        h = _mix(row[:, None] ^ col[None, :]) >> np.uint32(8)
    u = h * 2.0 ** -24
    p = market["price_lo"] + market["price_mean"] * (-np.log1p(-u))
    return np.minimum(p, market["price_hi"])


# -- realization --------------------------------------------------------------

def _views(price, bid, slot, dt):
    """Cumulative available time A and spot payment C at slot boundaries."""
    avail = price <= bid + 1e-12
    n = len(price)
    A = np.zeros(n + 1, dt)
    C = np.zeros(n + 1, dt)
    A[1:] = np.cumsum(np.where(avail, slot, 0.0).astype(dt))
    C[1:] = np.cumsum(np.where(avail, price * slot, 0.0).astype(dt))
    return A, C


def _at(cum, t, slot, horizon, dt):
    """cum(t): linear inside each slot."""
    n = len(cum) - 1
    t = np.clip(t, 0.0, horizon).astype(dt)
    k = np.clip((t / dt(slot)).astype(np.int64), 0, n - 1)
    frac = t - (k * dt(slot)).astype(dt)
    return cum[k] + (cum[k + 1] - cum[k]) / dt(slot) * frac


def _first(cum, target, slot, dt):
    """Earliest t with cum(t) >= target (cum has slopes 0 and 1)."""
    n = len(cum) - 1
    k = np.searchsorted(cum, target, side="left")
    kk = np.clip(k, 1, n)
    t = (kk - 1) * dt(slot) + (target - cum[kk - 1])
    t = np.where(k == 0, dt(0.0), t)
    return np.where(k > n, np.inf, t).astype(dt)


def _chains(A, C, slot, arrival, ends, z_t, d_eff, pins, dt):
    """Early-start chain realization of rows (arrival (R,), plans (R, L))."""
    n = len(A) - 1
    horizon = n * slot
    Hc = (np.arange(n + 1) * dt(slot)).astype(dt) - A
    cur = arrival.astype(dt)
    R, L = ends.shape
    spot = np.zeros(R, dt)
    od = np.zeros(R, dt)
    for k in range(L):
        end = ends[:, k]
        live = end > cur - dt(_WORK_EPS)
        start = np.minimum(cur, end)
        z = np.where(live, z_t[:, k], 0.0).astype(dt)
        dd = np.maximum(d_eff[:, k], 0.0).astype(dt)
        active = z > _WORK_EPS
        need = z / np.where(dd > 0, dd, dt(1.0))
        A0 = _at(A, start, slot, horizon, dt)
        C0 = _at(C, start, slot, horizon, dt)
        window = end - start
        no_flex = window - need <= np.maximum(
            dt(_WORK_EPS), np.maximum(dt(_FLEX_REL) * window,
                                      dt(_FLEX_ABS) * end))
        turn = np.where(no_flex, start,
                        _first(Hc, (start - A0) + window - need, slot, dt))
        fin = _first(A, A0 + need, slot, dt)
        on_spot = fin <= turn
        t_end = np.minimum(np.where(on_spot, fin, turn), end)
        got = np.maximum(_at(A, t_end, slot, horizon, dt) - A0, 0.0)
        s_work = np.minimum(dd * got, z)
        s_cost = dd * np.maximum(_at(C, t_end, slot, horizon, dt) - C0, 0.0)
        spot += np.where(active, s_cost, 0.0).astype(dt)
        od += np.where(active, z - s_work, 0.0).astype(dt)
        finish = np.where(active, np.where(on_spot, fin, end), start)
        finish = np.where(pins[:, k], end, finish)
        moved = (z_t[:, k] > _WORK_EPS) | pins[:, k]
        cur = np.where(moved, finish, cur).astype(dt)
    return spot, od


def policy_grid(cfg: dict):
    """[(beta, bid, beta0 or None)] in the program's grid order."""
    g = cfg["policy_grid"]
    b0s = g.get("beta0") or [None]
    return [(b2, b, b0) for b0 in b0s for b2 in g["beta"] for b in g["bid"]]


def dealloc_param(beta, beta0, r_total):
    """Which parameter drives Dealloc (Alg. 2 lines 1-5)."""
    if r_total > 0 and beta0 is not None and beta0 <= beta:
        return beta0
    return beta


class Windows:
    """Dealloc windows of the stream per parameter, each worked out once."""

    def __init__(self, chains):
        self.arr, self.dl, self.z, self.d, self.mask = pad(chains)
        self._ends = {}

    def __call__(self, x):
        """(starts, ends) of every job under Dealloc(x)."""
        if x not in self._ends:
            ends = windows(self.z, self.d, self.mask, self.arr, self.dl, x)
            starts = np.concatenate([self.arr[:, None], ends[:, :-1]], axis=1)
            self._ends[x] = (starts, ends)
        return self._ends[x]


def unit_costs(cfg: dict, chains, idx, n_slots: int, dtype=np.float64,
               avail=None, wins: Windows | None = None):
    """(S, J, P) unit costs of the policy grid in markets ``idx``.

    ``chains`` are the reference's own chain pseudo-jobs of the stream;
    ``idx`` the global scenario indices of the market family. The pool is
    dedicated: every policy may hold up to ``r_total`` self-owned instances
    over any window (the counterfactual scoring of Alg. 4), or, with
    ``avail`` (one query per market, ``(starts, ends) -> (J, L)``), what the
    query says is free over each window (Alg. 4's pool refinement).
    """
    dt = np.dtype(dtype).type
    m = cfg["market"]
    slot = 1.0 / m["slots_per_unit"]
    r_total = cfg["r_total"]
    wins = wins or Windows(chains)
    arr, z, d, mask = wins.arr, wins.z, wins.d, wins.mask
    J = len(arr)
    workload = np.maximum(z.sum(axis=1), 1e-12)
    pols = policy_grid(cfg)
    price = prices(m, idx, n_slots)
    out = np.zeros((len(idx), J, len(pols)))
    plans = {}
    for s in range(len(idx)):
        views, done = {}, {}
        for p, (b2, bid, b0) in enumerate(pols):
            x = dealloc_param(b2, b0, r_total)
            if (x, b0, bid) not in done:   # policies that plan alike
                key = (x, b0, None if avail is None else s)
                if key not in plans:
                    starts, ends = wins(x)
                    if r_total > 0 and b0 is not None:
                        a = r_total if avail is None else avail[s](starts,
                                                                   ends)
                        r = selfowned(z, d, mask, starts, ends, b0, a)
                    else:
                        r = np.zeros_like(z)
                    plans[key] = (ends,) + residuals(z, d, starts, ends, r)
                ends, z_t, d_eff, pins = plans[key]
                if bid not in views:
                    views[bid] = _views(price[s], bid, slot, dt)
                A, C = views[bid]
                sc, oc = _chains(A, C, slot, arr, ends.astype(dt),
                                 z_t.astype(dt), d_eff.astype(dt), pins, dt)
                done[(x, b0, bid)] = (
                    sc.astype(np.float64)
                    + m["p_ondemand"] * oc.astype(np.float64)) / workload
            out[s, :, p] = done[(x, b0, bid)]
    return out


# -- Alg. 4: the learner and the shared pool ---------------------------------

def hedge(C, arrival, d: float, u):
    """Exponentiated weights over the (J, P) unit costs C (Alg. 4).

    Job j draws its policy at its arrival from the current weights, by
    inverse CDF against the uniform ``u[j]``; its cost row enters the
    weights once its window has elapsed, at ``a_j + d``, with the learning
    rate sqrt(2 log P / (d max(t - d, d))). At equal times draws come
    first. Returns the drawn policies and the final weights.
    """
    J, P = C.shape
    arrival = np.asarray(arrival, np.float64)
    events = sorted([(float(arrival[j]), 0, j) for j in range(J)]
                    + [(float(arrival[j] + d), 1, j) for j in range(J)])
    t = arrival + d
    eta = np.sqrt(2.0 * np.log(P) / (d * np.maximum(t - d, d)))
    logw = np.full(P, -np.log(P))
    chosen = np.zeros(J, np.int64)
    for _, kind, j in events:
        if kind == 0:
            w = np.exp(logw - logw.max())
            cdf = np.cumsum(w / w.sum())
            cdf /= cdf[-1]
            chosen[j] = min(int(np.searchsorted(cdf, u[j], side="right")),
                            P - 1)
        else:
            logw = logw - eta[j] * C[j]
            logw = logw - logw.max()
    w = np.exp(logw - logw.max())
    return chosen, w / w.sum()


def _slots(starts, ends, slot, n):
    """Slots [k1, k2) a window occupies: every slot it overlaps."""
    k1 = np.maximum(np.floor(starts / slot + 1e-9).astype(np.int64), 0)
    k2 = np.minimum(np.ceil(ends / slot - 1e-9).astype(np.int64), n)
    return k1, np.maximum(k2, k1 + 1)


def allocate(starts, ends, z, d, mask, beta0, r_total: int, spu: int):
    """The shared pool: tasks in order of planned start each take what
    policy (12) asks, up to what is free over every slot of their window.
    Returns (J, L) counts and the pool's per-slot occupancy."""
    slot = 1.0 / spu
    n = int(np.ceil(max(float(ends[mask].max()), 1.0) * spu)) + 1
    used = np.zeros(n, np.int64)
    r = np.zeros_like(z)
    if r_total <= 0:
        return r, used
    cap = selfowned(z, d, mask, starts, ends, beta0[:, None], np.inf)
    cap = np.where(np.isnan(beta0)[:, None], 0.0, cap)   # no beta0: no pool
    k1, k2 = _slots(starts, ends, slot, n)
    jj, kk = np.nonzero(mask)
    flat_starts = starts[jj, kk]
    for i in np.argsort(flat_starts, kind="stable"):
        j, k = jj[i], kk[i]
        if cap[j, k] <= 0 or ends[j, k] - starts[j, k] <= 1e-12:
            continue
        free = r_total - int(used[k1[j, k]:k2[j, k]].max())
        g = min(int(cap[j, k]), free)
        if g > 0:
            used[k1[j, k]:k2[j, k]] += g
            r[j, k] = g
    return r, used


def residual_query(used, r_total: int, spu: int):
    """Instances of the pool left free over a window by a realized
    occupancy: r_total minus the occupancy's maximum over the window's
    slots (a window past the occupancy's end finds the pool free)."""
    slot = 1.0 / spu
    n = len(used)
    levels = [used.astype(np.int64)]
    while 2 ** len(levels) <= n:
        h = 2 ** (len(levels) - 1)
        levels.append(np.maximum(levels[-1][:-h], levels[-1][h:]))

    def query(starts, ends):
        lo = np.floor(starts / slot + 1e-9).astype(np.int64)
        hi = np.ceil(ends / slot - 1e-9).astype(np.int64)
        hi = np.maximum(hi, lo + 1)
        lo, hi = np.clip(lo, 0, n), np.clip(hi, 0, n)
        length = hi - lo
        top = np.zeros(lo.shape)
        ok = length > 0
        k = np.floor(np.log2(np.where(ok, length, 1))).astype(np.int64)
        for kk in np.unique(k[ok]):
            sel = ok & (k == kk)
            t = levels[kk]
            top[sel] = np.maximum(t[lo[sel]], t[hi[sel] - 2 ** kk])
        return np.maximum(r_total - top, 0.0)

    return query


def realize(cfg: dict, wins: Windows, pols, chosen, price):
    """The stream run once, each job under its drawn policy, against one
    shared pool and one market (f64 prices of its slots). Returns per-job
    cost, per-job self-owned work, and the pool's occupancy."""
    m = cfg["market"]
    spu = m["slots_per_unit"]
    slot = 1.0 / spu
    r_total = cfg["r_total"]
    arr, z, d, mask = wins.arr, wins.z, wins.d, wins.mask
    J, L = z.shape
    starts, ends = np.zeros((J, L)), np.zeros((J, L))
    beta0, bid = np.full(J, np.nan), np.zeros(J)
    for j, c in enumerate(chosen):
        b2, b, b0 = pols[c]
        s, e = wins(dealloc_param(b2, b0, r_total))
        starts[j], ends[j] = s[j], e[j]
        bid[j] = b
        beta0[j] = np.nan if b0 is None else b0
    r, used = allocate(starts, ends, z, d, mask, beta0, r_total, spu)
    z_t, d_eff, pins = residuals(z, d, starts, ends, r)
    cost = np.zeros(J)
    for b in np.unique(bid):
        rows = bid == b
        A, C = _views(price, b, slot, np.float64)
        sc, oc = _chains(A, C, slot, arr[rows], ends[rows], z_t[rows],
                         d_eff[rows], pins[rows], np.float64)
        cost[rows] = sc + m["p_ondemand"] * oc
    so_work = np.minimum(r * (ends - starts), z).sum(axis=1)
    return cost, so_work, used


def tola(cfg: dict, chains, idx, seeds, n_slots: int, dtype=np.float64):
    """Alg. 4 with one pool refinement in each market of ``idx``.

    Round 0 scores the grid against a dedicated pool; the learner of market
    s, drawing from ``np.random.default_rng(seeds[s])``, picks each job's
    policy; the picks run against the shared pool; round 1 re-scores the
    grid against the pool that run left free, and the learner goes again,
    on the next uniforms of its stream. ``dtype`` is the float type of the
    cost tensors; the learner and the realized run are float64.

    Returns the two rounds' (S, J, P) costs and, of the last round, the
    drawn policies (S, J), final weights (S, P), per-job realized cost and
    self-owned work (S, J).
    """
    wins = Windows(chains)
    J = len(wins.arr)
    d = max(c[1] - c[0] for c in chains)
    pols = policy_grid(cfg)
    price = prices(cfg["market"], idx, n_slots)
    rngs = [np.random.default_rng(sd) for sd in seeds]
    out = {"C": []}
    avail = None
    for _ in range(2 if cfg["r_total"] > 0 else 1):
        C = unit_costs(cfg, chains, idx, n_slots, dtype, avail, wins)
        out["C"].append(C)
        rounds = []
        for s in range(len(idx)):
            chosen, w = hedge(C[s], wins.arr, d, rngs[s].random(J))
            rounds.append((chosen, w) + realize(cfg, wins, pols, chosen,
                                                price[s]))
        avail = [residual_query(rd[4], cfg["r_total"],
                                cfg["market"]["slots_per_unit"])
                 for rd in rounds]
    for i, k in enumerate(("chosen", "weights", "cost", "selfowned")):
        out[k] = np.stack([rd[i] for rd in rounds])
    return out


def compare(got, ref, workload) -> dict:
    """The numbers ``correct`` is decided on, for one (S, J, P) tensor.

    ``cell_p99`` / ``cell_max``: 99th percentile and largest absolute gap
    of a cell's unit cost; ``alpha_max``: largest gap of a policy's stream
    average unit cost (sum of costs over sum of work) in one market.
    """
    gap = np.abs(np.asarray(got, np.float64) - ref)
    if not np.all(np.isfinite(gap)):
        return {"cell_p99": np.inf, "cell_max": np.inf, "alpha_max": np.inf}
    w = np.asarray(workload, np.float64)[None, :, None]
    alpha = lambda u: (u * w).sum(axis=1) / w.sum()
    return {"cell_p99": float(np.quantile(gap, 0.99)),
            "cell_max": float(gap.max()),
            "alpha_max": float(np.abs(alpha(got) - alpha(ref)).max())}


def compare_tola(got: dict, ref: dict, workload) -> dict:
    """The numbers ``correct`` is decided on, for one TOLA run.

    ``c0_*`` / ``c1_*``: 99th percentile and largest gap of a cell's unit
    cost in round 0 and in the refinement round; ``alpha_max``: largest gap
    of a policy's stream-average unit cost over both rounds;
    ``chosen_mismatch``: share of jobs whose drawn policy differs;
    ``weights_tv``: largest total-variation distance of the final weights;
    ``realized_gap``: largest gap of the realized stream-average unit cost;
    ``selfowned_gap``: largest gap of the realized self-owned share of the
    work.
    """
    w = np.asarray(workload, np.float64)
    W = w.sum()
    out = {}
    alpha = 0.0 if len(got["C"]) == len(ref["C"]) else np.inf
    for r, b in enumerate(ref["C"]):
        a = got["C"][r] if r < len(got["C"]) else np.full(b.shape, np.nan)
        gap = np.abs(np.asarray(a, np.float64) - b)
        if not np.all(np.isfinite(gap)):
            gap = np.full(gap.shape, np.inf)
        out[f"c{r}_p99"] = float(np.quantile(gap, 0.99))
        out[f"c{r}_max"] = float(gap.max())
        avg = lambda u: (u * w[None, :, None]).sum(axis=1) / W
        alpha = max(alpha, float(np.abs(avg(np.asarray(a, np.float64))
                                        - avg(b)).max()))
    out["alpha_max"] = alpha
    out["chosen_mismatch"] = float(np.mean(np.asarray(got["chosen"])
                                           != ref["chosen"]))
    out["weights_tv"] = float(
        0.5 * np.abs(np.asarray(got["weights"]) - ref["weights"]).sum(axis=1)
        .max())
    out["realized_gap"] = float(np.abs(
        np.asarray(got["cost"]).sum(axis=1) - ref["cost"].sum(axis=1)).max()
        / W)
    out["selfowned_gap"] = float(np.abs(
        np.asarray(got["selfowned"]).sum(axis=1)
        - ref["selfowned"].sum(axis=1)).max() / W)
    return {k: (v if np.isfinite(v) else np.inf) for k, v in out.items()}
