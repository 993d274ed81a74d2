"""The plain reference of an OL4EL run (the paper's §IV, as the repo's
compiled programs state it), in numpy, at a chosen precision.

Written from the algorithm, not from the program: it imports nothing of
``repro``.  What it shares with the program is the specification of the
run's random streams — ``jax.random`` keys derived from ``seed + 17``
(the same splits and ``fold_in``\\ s), so the reference draws the same
minibatch rows and the same Gumbel noise for arm selection.  JAX is used
for those draws only, on the CPU.

Sync round (one shared bandit, every edge blocks on the slowest):
    select an interval I by the OL4EL 3-step rule (UCB utility density x
    frequency floor(B_res / c_I), uniform over untried arms first);
    every edge runs I local steps from the global params; the global
    params become the n_e-weighted mean; every edge is charged the
    slowest edge's I*comp_e + comm; the utility (accuracy gain, or
    1/(1 + |delta params|)) updates the bandit.
Async event (one bandit per edge):
    the edge with the earliest finish completes its block; the global
    params mix it in with alpha = alpha0 / (1 + staleness / E); the edge
    pays its block's cost, updates its bandit, refetches the global
    params and schedules its next block if its budget allows.

The model's arithmetic is the workload's (``round``, ``event``,
``mix``, ``metric``, ``utility``, ``params``, ``host``): ``Workload``
here computes it in numpy at a chosen precision from the configuration's
reference; ``bench/checks/device-f32.py`` computes it on the device.
The control plane (selection weights, costs, the ledger, the streams) is
computed here, at the workload's precision ``P``.

``simulate_*`` runs free (its own decisions: the control) or follows a
recorded run's decisions (``forced``: the check).  Following, it still
draws its own choice at every decision and reports how far its own best
arm lies above the recorded one (``select_gap``), so a wrong decision
shows even though the replay goes on along the recorded path.

``replay`` follows a record once with a reference whose local step
takes an assignment (``local_step(..., pick=)``, K-means) and names its
``TIE_WIDTH``: it logs each near tie that the step meets (``Ties``) and
assigns the ones it is given the other way.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional

import numpy as np

from benchlib.prec import F64, Prec

Params = Dict[str, np.ndarray]

#: the fields of a run that the reference models; a mix that sets any
#: other field of the program's run configuration is refused, not
#: replayed as if the field were not there
MODELLED = frozenset({"mode", "seed", "budget", "ucb_c", "heterogeneity",
                      "async_alpha", "max_rounds", "init"})


def modelled(run: dict) -> dict:
    """``run``, once every field of it is one the reference models."""
    extra = sorted(set(run) - MODELLED)
    if extra:
        raise NotImplementedError(
            f"the reference does not model the run fields {extra}")
    return run


# -- control-plane arithmetic ------------------------------------------------


def edge_costs(cfg: dict, heterogeneity: float) -> Dict[str, np.ndarray]:
    """Per-edge compute / communication cost of one block (edge 0 is the
    fastest; the slowest is ``heterogeneity`` times slower)."""
    n = cfg["n_edges"]
    speed = (np.ones(1) if n == 1 else
             1.0 + (heterogeneity - 1.0) * np.arange(n) / (n - 1))
    comp = cfg["comp_cost"] * speed
    comm = np.full(n, float(cfg["comm_cost"]))
    return {"comp": comp, "comm": comm, "min_cost": comp + comm,
            "intervals": np.arange(1, cfg["max_interval"] + 1, dtype=float)}


def select_weights(counts, usum, t, resid, costs, ucb_c, P=F64
                   ) -> np.ndarray:
    """OL4EL selection weights over the interval arms, at precision
    ``P``."""
    feasible = costs <= resid + 1e-12
    untried = feasible & (counts == 0)
    if untried.any():
        return untried.astype(float)
    n = np.maximum(counts, 1)
    ucb = P.r(P.r(usum / n)
              + P.r(np.sqrt(P.r(ucb_c * P.r(math.log(max(t, 2))) / n))))
    density = P.r(ucb / np.maximum(costs, 1e-9))
    d = P.r(density - np.min(np.where(feasible, density, np.inf)) + 1e-9)
    freq = np.where(feasible, np.floor(P.r(resid / costs)), 0.0)
    with np.errstate(invalid="ignore"):
        return np.where(feasible, np.maximum(d * freq, 1e-12), 0.0)


def draw(w: np.ndarray, gumbel: np.ndarray, forced: Optional[int]):
    """``(arm, gap)``: the Gumbel-max draw over ``log w``, or the forced
    arm with the gap by which the draw's best perturbed log-weight lies
    above the forced arm's (0 when they agree, inf when the forced arm
    has no weight).  Returns arm -1 when nothing is affordable."""
    if not np.any(w > 0):
        return (-1 if forced is None else forced), (
            0.0 if forced is None or forced < 0 else math.inf)
    score = np.where(w > 0, np.log(np.maximum(w, 1e-30)), -np.inf) \
        + gumbel.astype(np.float64)
    best = int(np.argmax(score))
    if forced is None:
        return best, 0.0
    if forced < 0 or w[forced] <= 0:
        return forced, math.inf
    return forced, float(score[best] - score[forced])


# -- random streams (the run's jax.random chain, on the CPU) -----------------


def _cpu():
    import jax
    return jax.devices("cpu")[0]


def _bucket(n: int) -> int:
    return max(64, 1 << (max(n, 1) - 1).bit_length())


@functools.lru_cache(maxsize=None)
def _sync_stream_fn(T, E, k, B, K):
    import jax
    import jax.numpy as jnp

    def f(seed):
        def step(rng, _):
            rng, k_sel, k_data = jax.random.split(rng, 3)
            g = jax.random.gumbel(k_sel, (K,), jnp.float32)

            def edge(e):
                ke = jax.random.fold_in(k_data, e)
                return jax.vmap(lambda s: jax.random.uniform(
                    jax.random.fold_in(ke, s), (B,)))(jnp.arange(k))

            return rng, (g, jax.vmap(edge)(jnp.arange(E)))

        return jax.lax.scan(step, jax.random.key(seed), None, length=T)[1]

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _async_stream_fn(T, E, k, B, K):
    import jax
    import jax.numpy as jnp

    def f(seed, edges):
        rng, k_sel0, _ = jax.random.split(jax.random.key(seed), 3)
        g0 = jax.vmap(lambda e: jax.random.gumbel(
            jax.random.fold_in(k_sel0, e), (K,), jnp.float32))(
            jnp.arange(E))

        def step(rng, e):
            rng, k_sel, k_data, _ = jax.random.split(rng, 4)
            g = jax.random.gumbel(jax.random.fold_in(k_sel, e), (K,),
                                  jnp.float32)
            kd = jax.random.fold_in(k_data, e)
            u = jax.vmap(lambda s: jax.random.uniform(
                jax.random.fold_in(kd, s), (B,)))(jnp.arange(k))
            return rng, (g, u)

        return g0, jax.lax.scan(step, rng, edges)[1]

    return jax.jit(f)


def sync_streams(seed: int, n: int, E: int, k: int, B: int, K: int):
    import jax
    T = _bucket(n)
    with jax.default_device(_cpu()):
        g, u = _sync_stream_fn(T, E, k, B, K)(np.int32(seed + 17))
    return np.asarray(g), np.asarray(u)


def async_streams(seed: int, edges: np.ndarray, E: int, k: int, B: int,
                  K: int):
    import jax
    T = _bucket(len(edges))
    e = np.zeros(T, np.int32)
    e[:len(edges)] = edges
    with jax.default_device(_cpu()):
        g0, (g, u) = _async_stream_fn(T, E, k, B, K)(np.int32(seed + 17),
                                                     e)
    return np.asarray(g0), np.asarray(g), np.asarray(u)


# -- the model and its data --------------------------------------------------


class Workload:
    """A configuration's model reference (its ``configs/<name>.py``)
    with the data it trains on, held at precision ``P``."""

    def __init__(self, cfg: dict, model, edges: List[dict], eval_set: dict,
                 P: Prec):
        self.cfg, self.model, self.P = cfg, model, P
        self.n = np.array([len(e["y"]) for e in edges], np.int64)
        n_max, dim = int(self.n.max()), edges[0]["x"].shape[-1]
        self.x = np.zeros((len(edges), n_max, dim), np.float32)
        self.y = np.zeros((len(edges), n_max), np.int64)
        for i, e in enumerate(edges):
            self.x[i, :len(e["y"])] = e["x"]
            self.y[i, :len(e["y"])] = e["y"]
        self.x = P.arr(self.x)
        self.eval = {"x": P.arr(eval_set["x"]),
                     "y": np.asarray(eval_set["y"])}
        self.w_agg = self.n / self.n.sum()
        #: the reference's tie width, and the ``Ties`` of the replay
        #: under way (``replay``)
        self.tie_width = getattr(model, "TIE_WIDTH", None)
        self.ties: Optional[Ties] = None

    def rows(self, edges: np.ndarray, u: np.ndarray):
        """Minibatch rows drawn by uniforms ``u`` [len(edges), B]: row =
        floor(u * n_e) in float32, as the run's sampler specifies."""
        idx = (u.astype(np.float32)
               * self.n[edges, None].astype(np.float32)).astype(np.int64)
        return self.x[edges[:, None], idx], self.y[edges[:, None], idx]

    def block(self, p: Params, edges: np.ndarray, interval: int,
              u: np.ndarray) -> Params:
        """``interval`` local steps on each of ``edges`` (``p`` leaves
        ``[len(edges), ...]``; ``u`` ``[len(edges), k, B]``)."""
        kw = {} if self.ties is None else {"pick": self.ties}
        for s in range(interval):
            x, y = self.rows(edges, u[:, s])
            p = self.model.local_step(self.P, self.cfg, p, x, y, **kw)
        return p

    def params(self, init: Params) -> Params:
        """The run's initial parameters at this precision."""
        return {k: self.P.arr(v) for k, v in init.items()}

    def round(self, p: Params, interval: int, u: np.ndarray) -> Params:
        """A sync round's model work: every edge runs ``interval`` steps
        from ``p`` (``u`` ``[E, k, B]``), then the n_e-weighted mean."""
        E = self.cfg["n_edges"]
        local = self.block(_stack(p, E), np.arange(E), interval, u)
        return {k: self.P.r(np.einsum("e...,e->...", v, self.w_agg))
                for k, v in local.items()}

    def event(self, p: Params, e: int, interval: int, u: np.ndarray
              ) -> Params:
        """An async event's model work: edge ``e`` runs ``interval``
        steps from ``p`` (``u`` ``[k, B]``)."""
        local = self.block(_stack(p, 1), np.array([e]), interval, u[None])
        return {k: v[0] for k, v in local.items()}

    def mix(self, g: Params, p: Params, alpha: float) -> Params:
        """The staleness-weighted merge ``(1 - alpha) g + alpha p``."""
        return _mix(self.P, g, p, alpha)

    def host(self, p: Params) -> Params:
        """``p`` as host arrays (a reference on the device copies)."""
        return p

    def metric(self, p: Params) -> float:
        return self.model.metric(self.P, self.cfg, p, self.eval)

    def utility(self, new: Params, old: Params, new_metric, prev_metric):
        if self.cfg["utility"] == "eval_gain":
            return new_metric - prev_metric
        sq = sum(float(np.sum((self.P.r(new[k] - old[k])) ** 2))
                 for k in new)
        return 1.0 / (1.0 + math.sqrt(sq))


class Ties:
    """An assignment ``pick(d2, scale)`` for a reference's local step:
    the argmin of the distances ``d2`` ``[..., K]``, logging each point
    whose two nearest centroids lie within ``width`` times its terms'
    ``scale`` (``|x|^2 + 2|x.c| + |c|^2``, on which float32's rounding
    of the program's distances rests) as ``(call, point)``, and
    assigning the points in ``flips`` to their second-nearest centroid
    instead."""

    def __init__(self, width: float, flips=frozenset()):
        self.width, self.flips = width, frozenset(flips)
        self.met: List[tuple] = []
        self.calls = 0

    def __call__(self, d2: np.ndarray, scale: np.ndarray) -> np.ndarray:
        order = np.argsort(d2, axis=-1, kind="stable").reshape(
            -1, d2.shape[-1])
        flat = d2.reshape(-1, d2.shape[-1])
        rows = np.arange(len(flat))
        margin = flat[rows, order[:, 1]] - flat[rows, order[:, 0]]
        near = np.flatnonzero(margin < self.width
                              * scale.reshape(len(flat), -1).max(axis=-1))
        a = order[:, 0].copy()
        for i in near:
            key = (self.calls, int(i))
            self.met.append(key)
            if key in self.flips:
                a[i] = order[i, 1]
        self.calls += 1
        return a.reshape(d2.shape[:-1])


def replay(wl, run: dict, record: dict, flips=frozenset()):
    """``(simulate(wl, run, forced=record), met)``: the replay along a
    record and the near ties it met (``Ties``; none where ``wl`` names no
    tie width), with the ties in ``flips`` assigned the other way."""
    if getattr(wl, "tie_width", None) is None:
        return simulate(wl, run, record), []
    wl.ties = Ties(wl.tie_width, flips)
    try:
        return simulate(wl, run, record), wl.ties.met
    finally:
        wl.ties = None


def _stack(p: Params, n: int) -> Params:
    return {k: np.repeat(v[None], n, axis=0) for k, v in p.items()}


def _mix(P, g: Params, e: Params, alpha: float) -> Params:
    return {k: P.r(P.r((1.0 - alpha) * g[k]) + P.r(alpha * e[k]))
            for k in g}


# -- one run -----------------------------------------------------------------


def simulate_sync(wl: Workload, run: dict,
                  forced: Optional[dict] = None) -> Dict[str, Any]:
    """A sync run: ``run`` holds ``seed``, ``budget``, ``ucb_c``,
    ``heterogeneity``, ``max_rounds`` and ``init`` params; ``forced`` a
    recorded run whose ``interval`` sequence the replay follows."""
    cfg, P = wl.cfg, wl.P
    modelled(run)
    E, K, B = cfg["n_edges"], cfg["max_interval"], cfg["batch"]
    c = edge_costs(cfg, run["heterogeneity"])
    worst = int(np.argmax(c["comp"]))
    costs = P.r(c["intervals"] * c["comp"][worst] + c["comm"][worst])
    budget, horizon = float(run["budget"]), int(run["max_rounds"])
    n_plan = horizon if forced is None else min(len(forced["interval"]),
                                                horizon)
    gum, uni = sync_streams(run["seed"], max(n_plan, 1), E, K, B, K)

    params = wl.params(run["init"])
    counts, usum = np.zeros(K, np.int64), np.zeros(K)
    t_pulls = 0
    consumed = np.zeros(E)
    wall = 0.0
    prev = wl.metric(params) if cfg["utility"] == "eval_gain" else math.nan
    out = {k: [] for k in ("interval", "metric", "utility", "consumed",
                           "wall")}
    gap = 0.0
    t = 0
    while True:
        resid = P.r(budget - consumed)
        go = (t < horizon and resid.min() >= costs.min() - 1e-12
              and not np.any(resid < c["min_cost"]))
        if forced is not None and t >= n_plan:
            break
        if not go:
            break
        w = select_weights(counts, usum, t_pulls, resid.min(), costs,
                           run["ucb_c"], P)
        arm, g = draw(w, gum[t], None if forced is None
                      else int(forced["interval"][t]) - 1)
        gap = max(gap, g)
        interval = arm + 1
        new = wl.round(params, interval, uni[t])
        slot = float(np.max(P.r(interval * c["comp"] + c["comm"])))
        consumed = P.r(consumed + slot)
        wall = float(P.r(wall + slot))
        m = wl.metric(new) if cfg["utility"] == "eval_gain" else math.nan
        u = wl.utility(new, params, m, prev)
        counts[arm] += 1
        usum[arm] = P.r(usum[arm] + u)
        t_pulls += 1
        for key, val in (("interval", interval), ("metric", m),
                         ("utility", u), ("consumed", consumed.sum()),
                         ("wall", wall)):
            out[key].append(val)
        params, prev = new, m
        t += 1
    would_go = bool(t < horizon
                    and (budget - consumed).min() >= costs.min() - 1e-12
                    and not np.any(budget - consumed < c["min_cost"]))
    rec = {k: np.asarray(v, np.float64) for k, v in out.items()}
    rec.update(n=t, final_params=wl.host(params),
               final_metric=wl.metric(params), edge=None)
    # a recorded run that stopped while the reference would go on (or
    # went on past the reference's stop) differs in its count
    extra = int(forced is not None and would_go)
    return {"record": rec, "select_gap": gap, "count_gap": extra}


def simulate_async(wl: Workload, run: dict,
                   forced: Optional[dict] = None) -> Dict[str, Any]:
    """An async run (K=1 events); ``forced`` a recorded run whose event
    edges and block intervals the replay follows."""
    cfg, P = wl.cfg, wl.P
    modelled(run)
    E, K, B = cfg["n_edges"], cfg["max_interval"], cfg["batch"]
    c = edge_costs(cfg, run["heterogeneity"])
    costs_ek = P.r(c["intervals"][None, :] * c["comp"][:, None]
                   + c["comm"][:, None])
    budget, horizon = float(run["budget"]), int(run["max_rounds"])
    alpha0 = float(run["async_alpha"])
    if forced is None:
        # a free run draws its own streams event by event; the edge of
        # each event is known only once it happens, so draw per event
        return _async_free(wl, run, c, costs_ek)
    f_edge = np.asarray(forced["edge"], np.int64)
    f_int = np.asarray(forced["interval"], np.int64)
    n_plan = min(len(f_edge), horizon)
    g0, gum, uni = async_streams(run["seed"], f_edge[:n_plan], E, K, B, K)
    # the d-th decision of edge e is the interval of e's d-th event
    plan: List[List[int]] = [[] for _ in range(E)]
    for e, i in zip(f_edge[:n_plan], f_int[:n_plan]):
        plan[e].append(int(i))
    nxt = [0] * E

    def forced_arm(e):
        d = nxt[e]
        nxt[e] += 1
        return plan[e][d] - 1 if d < len(plan[e]) else -1

    st = _async_state(wl, run, E, K)
    gap, mismatches = 0.0, 0
    for e in range(E):
        g, miss = _schedule(st, c, costs_ek, run, e, g0[e], forced_arm(e),
                            wall=0.0)
        gap, mismatches = max(gap, g), mismatches + miss
    out = {k: [] for k in ("interval", "metric", "utility", "consumed",
                           "wall", "edge")}
    for t in range(n_plan):
        if not np.isfinite(st["finish"]).any():
            mismatches += n_plan - t           # the record goes on
            break
        e_ref = int(np.argmin(st["finish"]))
        e = int(f_edge[t])
        mismatches += int(e_ref != e)
        rec_vals = _event(wl, st, c, alpha0, e, uni[t])
        g, miss = _schedule(st, c, costs_ek, run, e, gum[t], forced_arm(e),
                            wall=rec_vals["wall"])
        gap, mismatches = max(gap, g), mismatches + miss
        for key in out:
            out[key].append(rec_vals[key])
    rec = {k: np.asarray(v, np.float64) for k, v in out.items()}
    rec.update(n=len(out["edge"]), final_params=wl.host(st["g"]),
               final_metric=wl.metric(st["g"]))
    return {"record": rec, "select_gap": gap, "count_gap": mismatches}


def _async_state(wl, run, E, K):
    g = wl.params(run["init"])
    return {"P": wl.P, "g": g, "fetched": [g] * E,
            "counts": np.zeros((E, K), np.int64), "usum": np.zeros((E, K)),
            "tp": np.zeros(E, np.int64), "consumed": np.zeros(E),
            "finish": np.full(E, np.inf), "infl_i": np.zeros(E, np.int64),
            "infl_c": np.zeros(E), "fetch_ver": np.zeros(E, np.int64),
            "version": 0, "wall": 0.0,
            "prev": (wl.metric(g) if wl.cfg["utility"] == "eval_gain"
                     else math.nan)}


def _schedule(st, c, costs_ek, run, e, gumbel, forced, wall):
    """Edge ``e`` selects its next block.  Returns ``(gap, miss)``: the
    decision gap, and 1 where a recorded stop (``forced == -1``) meets a
    budget that still affords a block, or a recorded block meets one
    that does not."""
    resid = float(st["P"].r(float(run["budget"]) - st["consumed"][e]))
    w = select_weights(st["counts"][e], st["usum"][e], st["tp"][e], resid,
                       costs_ek[e], run["ucb_c"], st["P"])
    able = bool(np.any(w > 0)) and resid >= c["min_cost"][e]
    if forced == -1:
        st["finish"][e] = np.inf
        return 0.0, int(able)
    arm, gap = draw(w, gumbel, forced)
    if not able:
        st["finish"][e] = np.inf
        return (0.0, 0) if forced is None else (0.0, 1)
    interval = arm + 1
    cost = float(st["P"].r(interval * c["comp"][e] + c["comm"][e]))
    st["finish"][e] = st["P"].r(wall + cost)
    st["infl_i"][e], st["infl_c"][e] = interval, cost
    return gap, 0


def _event(wl, st, c, alpha0, e, u) -> Dict[str, float]:
    P, E = wl.P, wl.cfg["n_edges"]
    wall = float(st["finish"][e])
    interval, cost = int(st["infl_i"][e]), float(st["infl_c"][e])
    p_new = wl.event(st["fetched"][e], e, interval, u)
    st["consumed"][e] = P.r(st["consumed"][e] + cost)
    alpha = float(P.r(alpha0 / P.r(
        1.0 + P.r((st["version"] - st["fetch_ver"][e]) / E))))
    new = wl.mix(st["g"], p_new, alpha)
    st["version"] += 1
    m = wl.metric(new) if wl.cfg["utility"] == "eval_gain" else math.nan
    u_val = wl.utility(new, st["g"], m, st["prev"])
    st["counts"][e, interval - 1] += 1
    st["usum"][e, interval - 1] = P.r(st["usum"][e, interval - 1] + u_val)
    st["tp"][e] += 1
    st["g"], st["prev"], st["wall"] = new, m, wall
    st["fetched"][e] = new
    st["fetch_ver"][e] = st["version"]
    return {"interval": interval, "metric": m, "utility": u_val,
            "consumed": float(st["consumed"].sum()), "wall": wall,
            "edge": e}


def _async_free(wl, run, c, costs_ek) -> Dict[str, Any]:
    """A free-running async run (the control): the event order is its
    own, so each event's streams are drawn once its edge is known."""
    cfg = wl.cfg
    E, K, B = cfg["n_edges"], cfg["max_interval"], cfg["batch"]
    horizon = int(run["max_rounds"])
    st = _async_state(wl, run, E, K)
    edges: List[int] = []
    g0, _, _ = async_streams(run["seed"], np.zeros(1, np.int32), E, K, B, K)
    for e in range(E):
        _schedule(st, c, costs_ek, run, e, g0[e], None, wall=0.0)
    out = {k: [] for k in ("interval", "metric", "utility", "consumed",
                           "wall", "edge")}
    t = 0
    while t < horizon and np.isfinite(st["finish"]).any():
        e = int(np.argmin(st["finish"]))
        edges.append(e)
        # the streams of event t depend on the edges of events < t only
        # through the chain position, so one draw with the known prefix
        _, gum, uni = async_streams(run["seed"], np.asarray(edges), E, K,
                                    B, K)
        vals = _event(wl, st, c, float(run["async_alpha"]), e, uni[t])
        _schedule(st, c, costs_ek, run, e, gum[t], None, wall=vals["wall"])
        for key in out:
            out[key].append(vals[key])
        t += 1
    rec = {k: np.asarray(v, np.float64) for k, v in out.items()}
    rec.update(n=t, final_params=wl.host(st["g"]),
               final_metric=wl.metric(st["g"]))
    return {"record": rec, "select_gap": 0.0, "count_gap": 0}


def simulate(wl, run: dict, forced: Optional[dict] = None
             ) -> Dict[str, Any]:
    """``simulate_sync`` or ``simulate_async``, by the run's ``mode``."""
    sim = simulate_sync if run["mode"] == "sync" else simulate_async
    return sim(wl, run, forced)
