"""``ELSession`` — the single façade over the OL4EL runtime.

    from repro.el import ELSession

    report = (ELSession(cfg)
              .with_executor(executor)            # any EdgeExecutor
              .with_policy("ol4el")               # name or Policy object
              .on_round(lambda rec: ...)          # streaming callbacks
              .run())                             # -> ELReport

One session owns the whole paper pipeline: the cloud coordinator (budgets
+ bandit), the utility estimator, the host-driven sync/async loops (the
§V simulator semantics), and — for jax-pure executors — the compiled
``run_sync_ingraph`` fast path that stages the entire budgeted loop into
one XLA program (see ``repro.el.ingraph``).

The legacy ``repro.federated.ELSimulator`` is now a deprecation shim over
this class.
"""

from __future__ import annotations

import dataclasses
import functools
import heapq
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import numpy as np

from repro.config import ExperimentConfig, OL4ELConfig
from repro.core.coordinator import CloudCoordinator
from repro.core.utility import UtilityEstimator, param_l2_delta
from repro.el import policies as el_policies
from repro.el.cache import ProgramCache
from repro.el.executor import EdgeExecutor, validate_executor
from repro.el.report import (ELReport, RoundRecord, records_from_out,
                             report_from_out)

Params = Any
RoundCallback = Callable[[RoundRecord], None]


def _on_host(params: Params) -> bool:
    """Whether every leaf of ``params`` is a host (numpy) array."""
    leaves = jax.tree.leaves(params)
    return bool(leaves) and all(isinstance(x, np.ndarray) for x in leaves)


def _session_call(mode: str):
    """Wrap a compiled entry point in its root ``session.call`` span
    (``repro.obs.trace``): the stage spans the call opens inside —
    ``session.prepare`` (with ``session.compile`` on a cache miss),
    ``session.dispatch``, ``session.records``, ``session.evaluate``,
    ``session.report`` — share its ``call`` id."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(self, *args, **kwargs):
            from repro.obs import trace as obs_trace
            with obs_trace.span("session.call", mode=mode):
                return fn(self, *args, **kwargs)
        return call
    return wrap


class ELSession:
    """Configure-then-run handle for one edge-cloud collaborative run."""

    def __init__(self, cfg: Union[OL4ELConfig, ExperimentConfig], *,
                 metric_name: str = "accuracy", lr: float = 0.1,
                 async_alpha: Optional[float] = None):
        if isinstance(cfg, ExperimentConfig):
            cfg = cfg.ol4el
        if async_alpha is not None:        # override the config's knob
            cfg = dataclasses.replace(cfg, async_alpha=float(async_alpha))
        self.cfg = cfg
        self.metric_name = metric_name
        self.lr = lr
        self._executor: Optional[EdgeExecutor] = None
        self._init_params: Optional[Params] = None
        self._n_samples: Optional[np.ndarray] = None
        self._policy: Optional[el_policies.Policy] = None
        self._callbacks: List[RoundCallback] = []
        self.coord: Optional[CloudCoordinator] = None   # built per run
        self._coord_consumed = False
        # compiled-program cache: key -> jitted program.  Keys carry the
        # structural config AND the mesh/sharding + donation identity
        # (two meshes compile different executables — sharing or
        # thrashing a slot between them would silently retrace per call).
        # Bounded FIFO (repro.el.cache.ProgramCache): each entry's
        # closure pins a device-resident copy of the padded per-edge
        # datasets, so an unbounded cache would leak under ever-changing
        # keys (e.g. fresh metric_fn lambdas).  A FleetServer can share
        # this cache (FleetServer(cache=session.compile_cache)) so its
        # cohorts and the session's verification runs count hits/misses
        # against one pool.
        self._max_cached_programs = 8
        self._programs = ProgramCache(self._max_cached_programs)
        self._closed = False
        self._fastpath = None                           # last sync program
        self._fastpath_key = None
        self._async_fastpath = None                     # last async program
        self._async_key = None
        self._sweep_program = None                      # last sweep program
        self._sweep_key = None

    @property
    def async_alpha(self) -> float:
        """The async staleness-mix base rate (a config knob since it is
        sweepable/traced; kept as an attribute for back-compat)."""
        return self.cfg.async_alpha

    # -- builder API ---------------------------------------------------------

    def with_executor(self, executor: EdgeExecutor, *,
                      init_params: Optional[Params] = None,
                      n_samples: Optional[Any] = None) -> "ELSession":
        validate_executor(executor)
        self._executor = executor
        self._init_params = init_params
        if n_samples is not None:
            self._n_samples = np.asarray(n_samples, np.float64)
        return self

    def with_policy(self, policy: Union[str, el_policies.Policy]
                    ) -> "ELSession":
        if isinstance(policy, str):
            self.cfg = dataclasses.replace(self.cfg, policy=policy)
            self._policy = None
        else:
            self._policy = policy
            self.cfg = dataclasses.replace(self.cfg, policy=policy.name)
        self.coord = None                    # any prepared coordinator is stale
        return self

    def with_metric(self, metric_name: str) -> "ELSession":
        self.metric_name = metric_name
        return self

    def on_round(self, callback: RoundCallback) -> "ELSession":
        """Register a streaming per-aggregation callback."""
        self._callbacks.append(callback)
        return self

    # -- internals -----------------------------------------------------------

    def _require_executor(self) -> EdgeExecutor:
        if self._closed:
            raise RuntimeError(
                "this ELSession is closed (close() released its compiled "
                "programs and device buffers); build a fresh session")
        if self._executor is None:
            raise RuntimeError("call .with_executor(...) before .run()")
        return self._executor

    def _initial_params(self) -> Params:
        if self._init_params is not None:
            if any(getattr(leaf, "is_deleted", lambda: False)()
                   for leaf in jax.tree.leaves(self._init_params)):
                raise RuntimeError(
                    "the session's init_params were donated to a previous "
                    "donate=True run (their buffers are invalidated); pass "
                    "fresh init_params via .with_executor() before running "
                    "again")
            return self._init_params
        ex = self._require_executor()
        if hasattr(ex, "init_params"):
            return ex.init_params(self.cfg.seed)
        raise RuntimeError(
            f"{type(ex).__name__} has no init_params(); pass "
            "init_params= to with_executor()")

    def coordinator(self) -> CloudCoordinator:
        """The current coordinator: before a run this is the instance the
        next run will use (budgets/costs inspectable — or adjustable);
        after a run it still holds that run's consumed state."""
        if self.coord is None:
            self.coord = CloudCoordinator(self.cfg, self.cfg.n_edges,
                                          lr=self.lr, policy=self._policy)
            self._coord_consumed = False
        return self.coord

    def _build(self) -> Tuple[CloudCoordinator, UtilityEstimator,
                              np.random.Generator]:
        if self._coord_consumed:             # each run starts from fresh
            self.coord = None                # budgets/bandit statistics
        coord = self.coordinator()
        self._coord_consumed = True
        utility = UtilityEstimator(self.cfg.utility)
        rng = np.random.default_rng(self.cfg.seed + 17)
        return coord, utility, rng

    def _emit(self, records: List[RoundRecord], rec: RoundRecord) -> None:
        records.append(rec)
        for cb in self._callbacks:
            cb(rec)

    def _snapshot(self, ex: EdgeExecutor, utility: UtilityEstimator,
                  params: Params, want_metric: bool) -> Dict[str, Any]:
        snap: Dict[str, Any] = {"params": params, "loss": 0.0}
        if want_metric or utility.kind in ("eval_gain", "loss_delta"):
            m = ex.evaluate(params)
            snap["metric"] = m[self.metric_name]
            snap["loss"] = m.get("loss", 0.0)
        else:
            snap["metric"] = float("nan")
        return snap

    def _report(self, ex: EdgeExecutor, coord: CloudCoordinator,
                params: Params, records: List[RoundRecord], reason: str,
                t0: float) -> ELReport:
        final = ex.evaluate(params)[self.metric_name]
        pulls = np.zeros(self.cfg.max_interval, np.int64)
        for b in coord.bandits:
            pulls += np.asarray(b.counts)
        return ELReport(
            records=records,
            final_metric=float(final),
            n_aggregations=len(records),
            total_consumed=coord.total_consumed(),
            wall_time=records[-1].wall_time if records else 0.0,
            terminated_reason=reason,
            policy=self.cfg.policy,
            mode=self.cfg.mode,
            arm_pulls=[int(c) for c in pulls],
            elapsed_s=time.perf_counter() - t0,
            final_params=params,
        )

    # -- host-driven synchronous loop ----------------------------------------

    def run_sync(self, max_rounds: int = 10_000,
                 eval_every: int = 1) -> ELReport:
        cfg = self.cfg
        ex = self._require_executor()
        coord, utility, rng = self._build()
        t0 = time.perf_counter()
        params = self._initial_params()
        records: List[RoundRecord] = []
        wall, n_agg = 0.0, 0
        prev = self._snapshot(ex, utility, params, want_metric=True)
        reason = "max_rounds"
        for _ in range(max_rounds):
            interval = coord.decide()
            if interval < 0 or coord.all_exhausted():
                reason = "budget_exhausted"
                break
            edge_params: List[Params] = []
            round_costs = np.zeros(cfg.n_edges)
            for e in range(cfg.n_edges):
                p_e, _ = ex.local_train(params, e, interval,
                                        rng.integers(1 << 31))
                edge_params.append(p_e)
                round_costs[e] = coord.realized_cost(e, interval)
            # Time-budget semantics (paper §V.A): synchronous edges BLOCK
            # on the slowest edge, so every edge's budget advances by the
            # straggler's round time.
            slot = float(round_costs.max())
            for e in range(cfg.n_edges):
                coord.charge(e, slot)
            wall += slot
            from repro.federated.aggregation import weighted_average
            w = (np.ones(cfg.n_edges) if self._n_samples is None
                 else self._n_samples)
            params = weighted_average(edge_params, w)
            n_agg += 1
            new = self._snapshot(ex, utility, params,
                                 want_metric=(n_agg % eval_every == 0))
            u = utility(prev, new)
            # sync: ONE bandit fed the worst-case (binding) cost
            coord.observe(0, interval, u, slot)
            if coord.ac is not None:
                self._update_ac(coord, edge_params, prev["params"], params,
                                interval)
            prev = new
            self._emit(records, RoundRecord(
                wall, coord.total_consumed(), new["metric"], u,
                interval, -1, n_agg))
        return self._report(ex, coord, params, records, reason, t0)

    # -- host-driven asynchronous (event-driven) loop ------------------------

    def run_async(self, max_events: Optional[int] = None,
                  eval_every: int = 1,
                  rng_streams: str = "numpy") -> ELReport:
        """The host-driven event-queue loop (paper §V.A async semantics).

        ``max_events=None`` derives the horizon from budget/cost
        (``repro.el.events.default_event_horizon``), so long runs are
        never silently truncated.

        ``rng_streams`` picks the randomness source: ``"numpy"`` (the
        legacy host streams) or ``"jax"`` — the same priority-queue loop
        driven by the compiled async program's ``jax.random`` chain and
        f32 kernels (``repro.el.events.reference``; needs the in-graph
        support matrix).  In fixed-cost mode the ``"jax"`` loop is
        bit-identical to ``run_async_ingraph()``; ``eval_every`` is
        ignored there (the bandits consume the utility every event).
        """
        cfg = self.cfg
        ex = self._require_executor()
        if rng_streams == "jax":
            from repro.el.events.reference import run_async_reference
            acfg = self._ingraph_cfg("run_async(rng_streams='jax')",
                                     mode="async")
            return run_async_reference(
                ex, acfg, self._initial_params(),
                metric_name=self.metric_name, max_events=max_events,
                callbacks=self._callbacks)
        if rng_streams != "numpy":
            raise ValueError(
                f"unknown rng_streams={rng_streams!r}; expected 'numpy' "
                "or 'jax'")
        if max_events is None:
            from repro.el.events.knobs import default_event_horizon
            max_events = default_event_horizon(cfg)
        coord, utility, rng = self._build()
        t0 = time.perf_counter()
        global_params = self._initial_params()
        records: List[RoundRecord] = []
        n_agg = 0
        prev = self._snapshot(ex, utility, global_params, want_metric=True)
        # per-edge in-flight blocks: (finish_time, edge, interval, cost) —
        # the SAME realized-cost draw sets the finish time AND is charged
        # at completion, so charged budget always equals simulated
        # wall-clock (one draw per block, not two independent ones).
        heap: List[Tuple[float, int, int, float]] = []
        fetch_version = np.zeros(cfg.n_edges)
        version = 0
        edge_params: List[Params] = [global_params] * cfg.n_edges
        for e in range(cfg.n_edges):
            i = coord.decide(e)
            if i < 0:
                continue
            cost = coord.realized_cost(e, i)
            heapq.heappush(heap, (cost, e, i, cost))
            fetch_version[e] = version
        wall = 0.0
        reason = "max_events"
        for _ in range(max_events):
            if not heap:
                reason = "budget_exhausted"
                break
            wall, e, interval, cost = heapq.heappop(heap)
            # edge e finishes `interval` local iterations and uploads
            p_e, _ = ex.local_train(edge_params[e], e, interval,
                                    rng.integers(1 << 31))
            coord.charge(e, cost)
            # staleness in *epochs*: normalize raw version staleness by the
            # fleet size so async mixing survives edge-count scaling
            staleness = (version - fetch_version[e]) / max(cfg.n_edges, 1)
            from repro.federated.aggregation import (staleness_alpha,
                                                     staleness_mix)
            alpha = staleness_alpha(self.async_alpha, staleness)
            global_params = staleness_mix(global_params, p_e, alpha)
            version += 1
            n_agg += 1
            new = self._snapshot(ex, utility, global_params,
                                 want_metric=(n_agg % eval_every == 0))
            u = utility(prev, new)
            coord.observe(e, interval, u, cost)
            prev = new
            self._emit(records, RoundRecord(
                wall, coord.total_consumed(), new["metric"], u,
                float(interval), e, n_agg))
            # edge fetches the fresh global model, schedules its next block
            edge_params[e] = global_params
            fetch_version[e] = version
            nxt = coord.decide(e)
            if nxt > 0 and not coord.exhausted(e):
                next_cost = coord.realized_cost(e, nxt)
                heapq.heappush(heap, (wall + next_cost, e, nxt, next_cost))
        return self._report(ex, coord, global_params, records, reason, t0)

    def run(self, **kw) -> ELReport:
        if self.cfg.mode == "sync":
            return self.run_sync(**kw)
        return self.run_async(**kw)

    # -- compiled fast path ---------------------------------------------------

    def _attach_cache_stats(self, report: ELReport,
                            key: Optional[tuple] = None) -> ELReport:
        """Fold the session's compile-cache counters into
        ``report.telemetry["cache"]`` (always present on fast-path
        reports — the cache exists whether or not rings were on).  When
        ``key`` names a cached program that has been profiled, its
        :class:`repro.obs.prof.ProgramProfile` snapshot joins as
        ``report.telemetry["profile"]``."""
        tele = dict(report.telemetry or {})
        tele["cache"] = self._programs.stats()
        if key is not None:
            prof = self._programs.profile(key)
            if prof is not None:
                tele["profile"] = prof.to_json()
        report.telemetry = tele
        return report

    def _profile_program(self, key: tuple, program: Any, params: Params,
                         example_args: tuple, *, mode: str, mesh,
                         donate: bool, profile: bool, contract,
                         scenario: bool = False,
                         exchange: bool = False) -> Any:
        """The dispatch-time half of the performance observatory
        (``repro.obs.prof``): lazily extract a ``ProgramProfile`` for
        the cached program (once per cache entry — the AOT compile
        behind it does not share the jit dispatch cache, so this is
        strictly opt-in) and, when a contract is armed, enforce it.

        ``profile`` / ``contract`` are the per-call opt-ins;
        ``REPRO_EL_PROFILE=1`` / ``REPRO_EL_CONTRACTS=1`` arm them
        process-wide.  ``contract=True`` checks the mode's
        ``default_contract`` (collective census + donation aliasing of
        the placed ``params``' on-device bytes);
        a ``CollectiveContract`` instance checks that.  Violations
        raise ``repro.obs.prof.ContractViolation`` before dispatch.
        """
        import os
        from repro.obs import prof as obs_prof, trace as obs_trace
        if contract is None and os.environ.get("REPRO_EL_CONTRACTS"):
            contract = True
        want_profile = (profile or bool(contract)
                        or bool(os.environ.get("REPRO_EL_PROFILE")))
        if not want_profile:
            return self._programs.profile(key)
        prof = self._programs.profile(key)
        if prof is None:
            with obs_trace.span("session.profile", mode=mode):
                prof = obs_prof.profile_jit(program, *example_args,
                                            donated=donate)
                self._programs.set_profile(key, prof)
        if contract:
            c = contract
            if c is True:
                c = obs_prof.default_contract(
                    mesh=mesh, donated=donate, mode=mode,
                    scenario=scenario, exchange=exchange,
                    param_bytes=obs_prof.param_tree_bytes(params))
            c.enforce(prof)
        return prof

    @staticmethod
    def _structural_cfg(cfg: OL4ELConfig) -> OL4ELConfig:
        """The config with the knob fields normalized away: ucb_c, budget,
        heterogeneity, cost noise, the async mixing rate and seed enter
        the compiled programs as traced inputs (``sync_knobs`` /
        ``async_knobs`` / the rng key), so cache keys built from this
        reuse one program across any knob point.  ``mode`` stays — it
        selects the sync round vs the async event-horizon program.  A
        scenario keeps only ``ScenarioSpec.structural()`` (presence +
        period — the schedule arrays' traced shape); churn rates, cost
        tails and the competing policy are knob values."""
        return dataclasses.replace(cfg, ucb_c=0.0, budget=0.0,
                                   heterogeneity=1.0, seed=0,
                                   cost_noise=0.0, cost_model="fixed",
                                   async_alpha=0.5,
                                   policy=(cfg.policy
                                           if cfg.scenario is None
                                           else "ol4el"),
                                   scenario=(None if cfg.scenario is None
                                             else cfg.scenario.structural()))

    def _ingraph_cfg(self, caller: str,
                     mode: Optional[str] = None) -> OL4ELConfig:
        """The effective (mode-coerced, support-checked) fast-path config;
        a ``mode`` names a single run (``run_*_ingraph``), none a sweep."""
        from repro.el.ingraph import check_ingraph_support
        cfg = self.cfg
        if mode is not None and cfg.mode != mode:
            cfg = dataclasses.replace(cfg, mode=mode)
        # an injected ol4el Policy object carries its own exploration
        # constant; honor it like the host path does (other policy objects
        # are rejected by the support check below)
        if self._policy is not None and self._policy.name == "ol4el":
            cfg = dataclasses.replace(cfg, ucb_c=self._policy.ucb_c)
        check_ingraph_support(cfg, self._require_executor(), caller=caller,
                              single_run=mode is not None)
        return cfg

    @property
    def compile_cache(self) -> ProgramCache:
        """The session's bounded compiled-program cache — pass it to a
        ``FleetServer(cache=...)`` to share one pool (and one hit/miss
        counter) between the server's cohorts and this session's
        independent verification runs."""
        return self._programs

    def clear_compile_cache(self) -> int:
        """Drop every cached compiled program AND the last-used aliases
        that keep evicted programs alive.  Each program's closure pins a
        device-resident copy of the padded per-edge datasets, so on a
        long-lived server this is what actually releases device memory
        (the buffers free once the GC collects the closures).  Returns
        the number of cached programs dropped; the session stays usable
        — the next run recompiles."""
        n = self._programs.clear()
        self._fastpath = self._fastpath_key = None
        self._async_fastpath = self._async_key = None
        self._sweep_program = self._sweep_key = None
        return n

    def close(self) -> None:
        """Release everything the session pins on device: the compiled
        programs (and the dataset copies their closures hold) plus the
        initial-params reference.  After ``close()`` the session refuses
        to run — build a fresh one instead (idempotent)."""
        self.clear_compile_cache()
        self._init_params = None
        self._executor = None
        self._closed = True

    def _cache_program(self, key: tuple, program: Any) -> Any:
        """Insert into the bounded FIFO program cache (oldest evicted;
        the last-used aliases keep an evicted program alive until the
        next run replaces them)."""
        self._programs.max_entries = self._max_cached_programs
        return self._programs.put(key, program)

    def _jit_ingraph(self, core, knob_names, mesh, donate, params):
        """jit one of the compiled EL programs with the run's placement
        and donation: with ``mesh`` the inputs land per
        ``repro.sharding.el_run_in_shardings`` (params by the per-arch
        resolver, control plane replicated); with ``donate`` the params
        argument's buffers are donated — XLA aliases them into the
        output params, so an aggregation updates the fleet's parameters
        in place instead of copying them every round.  ``params`` is the
        run's already-materialized initial tree (shapes only are read)."""
        kw: Dict[str, Any] = {}
        if donate:
            kw["donate_argnums"] = (0,)
        if mesh is not None:
            from repro.sharding import el_run_in_shardings
            ex = self._require_executor()
            kw["in_shardings"] = el_run_in_shardings(
                mesh, getattr(ex.model, "cfg", None),
                jax.eval_shape(lambda p: p, params), knob_names)
        return jax.jit(core, **kw)

    def _finish_ingraph(self, ex: EdgeExecutor, cfg: OL4ELConfig,
                        key: tuple, out: Dict[str, Any], params: Params,
                        mode: str, horizon: int, t0: float,
                        to_host: bool = False) -> ELReport:
        """The host half of a compiled single run after its dispatch,
        one stage span each: the round records (and the callbacks), the
        final evaluation, the report.  Both are built from one host copy
        of ``out``: ``device_get`` starts every leaf's transfer before
        waiting on any, where reading a device array element by element
        costs a gather and a blocking copy per element.  ``params``
        stays on the device for ``ex.evaluate``, then, ``to_host``, goes
        to the host for the report.  The records span carries the
        program's counters where it has them: the local steps and
        tokens each edge trained (``edge_steps``, ``edge_tokens``)."""
        from repro.obs import trace as obs_trace
        with obs_trace.span("session.records") as sp:
            out = jax.device_get(out)
            sp["host_bytes"] = sum(x.nbytes for x in jax.tree.leaves(out))
            for counter in ("edge_steps", "edge_tokens"):
                if counter in out:
                    sp[counter] = out[counter]
            records: List[RoundRecord] = []
            for rec in records_from_out(out, 0, int(out["n_rounds"])):
                self._emit(records, rec)
            sp["n"] = len(records)
        with obs_trace.span("session.evaluate", n=1):
            final = ex.evaluate(params)[self.metric_name]
        with obs_trace.span("session.report"):
            if to_host:
                params = jax.device_get(params)
            report = report_from_out(
                out, mode=mode, policy=cfg.policy, horizon=horizon,
                final_metric=final, final_params=params,
                elapsed_s=time.perf_counter() - t0, records=records)
            return self._attach_cache_stats(report, key)

    @_session_call("sync")
    def run_sync_ingraph(self, max_rounds: int = 512,
                         metric_fn: Optional[Callable] = None, *,
                         mesh=None, donate: bool = False,
                         telemetry=None, profile: bool = False,
                         contract=None) -> ELReport:
        """Run the whole budgeted sync loop as ONE compiled XLA program.

        Numerically equivalent (up to RNG streams) to ``run_sync`` under
        the fast path's contract — the supported matrix (see
        ``repro.el.ingraph``; shared with ``run_async_ingraph``) is:

        ============  =====================================================
        mode           ``sync`` (this method) or ``async``
                       (``run_async_ingraph``, the ``repro.el.events``
                       event-horizon program)
        policy         ``ol4el`` only (the compiled 3-step KUBE bandit;
                       shared in sync, per-edge in async)
        cost_model     ``fixed`` or ``variable`` (in-graph cost noise)
        utility        ``eval_gain`` (jittable metric) or ``param_delta``
        executor       ``InGraphExecutor`` (e.g. ``ClassicExecutor``;
                       ``TokenLMExecutor``, a language model, here only)
        ============  =====================================================

        Unsupported (policy, cost_model, executor) combinations raise an
        informative ``ValueError``/``TypeError`` naming the combination.
        Callbacks still fire, streamed after the device loop finishes.

        ``mesh=`` runs the program sharded: the ``[n_edges, ...]`` data
        plane over the mesh's (``pod``, ``data``) axes, model tensors
        over ``model``, control plane replicated — bit-identical to the
        mesh-less program (see ``make_sync_program``).  ``donate=True``
        donates the initial params' buffers to the program (in-place
        fleet update); the caller must not reuse the passed-in params
        afterwards — the session detects a reuse attempt and raises.
        Initial params held as numpy arrays are placed afresh for each
        run and always donated, and the report's ``final_params`` come
        back as numpy: a model the size of a chip is on it once per run.

        ``telemetry=`` switches the in-graph observability rings on
        (``repro.obs``: None/False off — today's program bit-for-bit;
        True/int/``TelemetrySpec`` on).  The recorded rings land in
        ``report.telemetry["rings"]``; the gate is part of the compile
        cache key, so on/off runs never share a program slot.

        ``profile=True`` extracts a ``repro.obs.prof.ProgramProfile``
        for the compiled program (XLA cost/memory analysis + the HLO
        collective census) — computed once per cached program, attached
        to the cache entry and surfaced as
        ``report.telemetry["profile"]``.  ``contract=`` additionally
        enforces a ``CollectiveContract`` at dispatch time (``True``:
        the mode's ``default_contract`` — gather-before-reduce census
        plus donation alias bytes; or a contract instance).
        ``REPRO_EL_PROFILE=1`` / ``REPRO_EL_CONTRACTS=1`` arm these
        process-wide; both default off (profiling costs one extra AOT
        compile per program).
        """
        from repro.el.ingraph import (is_token_data, make_sync_program,
                                      sync_knob_names, sync_knobs)
        from repro.obs import rings as obs_rings, trace as obs_trace
        with obs_trace.span("session.prepare") as sp:
            ex = self._require_executor()
            cfg = self._ingraph_cfg("run_sync_ingraph", mode="sync")
            spec = obs_rings.as_spec(telemetry)
            t0 = time.perf_counter()
            params = self._initial_params()
            # params kept on the host are placed afresh for every run:
            # that copy is the program's own, so it is donated, and the
            # trained params go back to the host (a device holds one
            # run's params at a time, whatever their size)
            on_host = _on_host(params)
            donate = donate or on_host
            key = ("sync", ex, self._structural_cfg(cfg), max_rounds,
                   metric_fn, self.metric_name,
                   None if self._n_samples is None
                   else tuple(self._n_samples),
                   mesh, donate, spec)
            program = self._programs.get(key)
            sp["cache"] = "miss" if program is None else "hit"
            if program is None:
                with obs_trace.span("session.compile", mode="sync",
                                    telemetry=spec is not None):
                    program = self._jit_ingraph(make_sync_program(
                        ex.model, ex.edge_data, ex.eval_set, cfg,
                        lr=ex.lr, batch=ex.batch,
                        n_samples=self._n_samples, metric_fn=metric_fn,
                        metric_name=self.metric_name,
                        max_rounds=max_rounds, mesh=mesh, telemetry=spec),
                        sync_knob_names(cfg), mesh, donate, params)
                    self._cache_program(key, program)
            self._fastpath, self._fastpath_key = program, key
            self._profile_program(
                key, program, params,
                (jax.eval_shape(lambda p: p, params),
                 jax.random.key(cfg.seed + 17), sync_knobs(cfg)),
                mode="sync", mesh=mesh, donate=donate, profile=profile,
                contract=contract, scenario=cfg.scenario is not None,
                exchange=is_token_data(ex.edge_data))
        with obs_trace.span("session.dispatch", mode="sync") as sp:
            params, out = jax.block_until_ready(
                program(params, jax.random.key(cfg.seed + 17),
                        sync_knobs(cfg)))
            sp["n_rounds"] = int(out["n_rounds"])
        return self._finish_ingraph(ex, cfg, key, out, params, "sync",
                                    max_rounds, t0, to_host=on_host)

    @_session_call("async")
    def run_async_ingraph(self, max_events: Optional[int] = None,
                          metric_fn: Optional[Callable] = None, *,
                          mesh=None, donate: bool = False,
                          telemetry=None, profile: bool = False,
                          contract=None) -> ELReport:
        """Run the whole budgeted async event loop as ONE compiled XLA
        program (``repro.el.events``): no host priority queue — finish
        times live in an ``[n_edges]`` array and each ``lax.while_loop``
        step pops the argmin finish time, staleness-merges that edge's
        block and schedules its next one.

        Same supported matrix as ``run_sync_ingraph`` (policy ``ol4el``
        with per-edge bandits).  ``max_events=None`` derives the event
        horizon from budget/cost (``default_event_horizon``), so runs
        terminate on budget exhaustion, never silent truncation.  An
        explicit ``max_events`` is **bucketed**: the compiled history
        arrays are sized at the next power of two
        (``bucket_event_horizon``) while the exact cap rides in as the
        traced ``event_cap`` knob — nearby caps share ONE executable
        instead of recompiling per value, and the loop still stops at
        exactly ``max_events`` events.  In fixed-cost mode the result is
        bit-identical to the host event queue on the same streams,
        ``run_async(rng_streams="jax")``.

        ``mesh=`` shards the per-edge datasets and the ``[n_edges, ...]``
        fetched-params stack over the mesh (bit-identical to the
        mesh-less program — see ``make_async_program``); ``donate=True``
        donates the initial params' buffers (caller must not reuse them;
        the session detects reuse and raises).  ``cfg.async_batch_k``
        sets the engine's K-event wave width (0 auto-tunes from the
        mesh: sharded runs dispatch batched waves, replicated runs keep
        the single-event program — see ``resolve_async_batch_k``); it is
        structural, so it participates in the compile-cache key.

        ``telemetry=`` switches the in-graph observability rings on
        (see ``run_sync_ingraph``; async rings additionally record the
        merge ``alpha``/staleness and event inter-arrival times).
        ``profile=`` / ``contract=`` attach a ``ProgramProfile`` and
        enforce dispatch-time collective contracts exactly as in
        ``run_sync_ingraph`` (the async default contract uses the same
        gather-before-reduce census).
        """
        from repro.el.events import (async_knob_names, async_knobs,
                                     bucket_event_horizon,
                                     make_async_program,
                                     padded_event_horizon)
        from repro.obs import rings as obs_rings, trace as obs_trace
        with obs_trace.span("session.prepare") as sp:
            ex = self._require_executor()
            cfg = self._ingraph_cfg("run_async_ingraph", mode="async")
            spec = obs_rings.as_spec(telemetry)
            t0 = time.perf_counter()
            if max_events is None:
                # the padded (power-of-two) horizon: it is part of the
                # compile cache key (it sizes the history arrays), so
                # keying the exact budget/cost-dependent value would
                # recompile on every knob change the traced inputs exist
                # to absorb
                horizon = padded_event_horizon(cfg)
                event_cap = None
            else:
                # explicit caps bucket the same way: the STATIC history
                # length is the pow-2 envelope, the exact cap is the
                # traced event_cap knob — nearby caps share one executable
                event_cap = int(max_events)
                horizon = bucket_event_horizon(event_cap)
            key = ("async", ex, self._structural_cfg(cfg), horizon,
                   metric_fn, self.metric_name, mesh, donate, spec)
            params = self._initial_params()
            program = self._programs.get(key)
            sp["cache"] = "miss" if program is None else "hit"
            if program is None:
                with obs_trace.span("session.compile", mode="async",
                                    telemetry=spec is not None):
                    program = self._jit_ingraph(make_async_program(
                        ex.model, ex.edge_data, ex.eval_set, cfg,
                        lr=ex.lr, batch=ex.batch, metric_fn=metric_fn,
                        metric_name=self.metric_name, max_events=horizon,
                        mesh=mesh, telemetry=spec),
                        async_knob_names(cfg), mesh, donate, params)
                    self._cache_program(key, program)
            self._async_fastpath, self._async_key = program, key
            knobs = async_knobs(cfg)
            if event_cap is not None:
                knobs["event_cap"] = np.int32(event_cap)
            self._profile_program(
                key, program, params,
                (jax.eval_shape(lambda p: p, params),
                 jax.random.key(cfg.seed + 17), knobs),
                mode="async", mesh=mesh, donate=donate, profile=profile,
                contract=contract, scenario=cfg.scenario is not None)
        with obs_trace.span("session.dispatch", mode="async") as sp:
            params, out = jax.block_until_ready(
                program(params, jax.random.key(cfg.seed + 17), knobs))
            sp["n_events"] = int(out["n_rounds"])
        return self._finish_ingraph(
            ex, cfg, key, out, params, "async",
            horizon if event_cap is None else event_cap, t0)

    # -- compiled ablation sweeps ---------------------------------------------

    @_session_call("sweep")
    def sweep(self, spec, *, mesh=None,
              metric_fn: Optional[Callable] = None, telemetry=None):
        """Run a whole ablation grid as ONE compiled, vmapped program.

        ``spec`` is a :class:`repro.el.sweep.SweepSpec` — grids over
        ``ucb_c`` / ``budget`` / ``heterogeneity`` / ``cost_noise`` /
        ``async_alpha`` / ``seeds``; empty axes inherit this session's
        config.  The session's ``cfg.mode`` picks the compiled program
        the grid vmaps over: the sync round (``repro.el.ingraph``) or
        the async event-horizon engine (``repro.el.events``).  Every
        cell is bit-identical to an independent ``run_sync_ingraph`` /
        ``run_async_ingraph`` with that cell's config (same RNG
        streams), and the same support matrix applies.  With ``mesh=``
        the sweep dim shards over the mesh's (``pod``, ``data``) axes.
        An async grid may sweep ``async_batch_k`` (the K-event wave
        width): each K is a different compiled body, so the session
        runs one vmapped sub-sweep per K (the axis is slowest-varying —
        sub-results concatenate back into the flattened grid order) and
        every K's cells remain bit-identical to each other.
        ``telemetry=`` switches the per-cell in-graph rings on (see
        ``run_sync_ingraph``); each cell's rings land stacked in the
        report's ``out["telemetry"]`` leaves.  Returns a
        :class:`repro.el.sweep.SweepReport`.
        """
        from repro.el.sweep.engine import (make_sweep_program,
                                           run_sweep_program)
        from repro.el.sweep.report import SweepReport
        from repro.obs import rings as obs_rings, trace as obs_trace
        with obs_trace.span("session.prepare") as sp:
            ex = self._require_executor()
            cfg = self._ingraph_cfg("ELSession.sweep")
            tele_spec = obs_rings.as_spec(telemetry)
            t0 = time.perf_counter()
            # each async_batch_k value is a different compiled wave body —
            # run one vmapped sub-sweep per K (a single-K / sync grid is
            # one sub-sweep: exactly the old path)
            subs = (spec.per_batch_k() if cfg.mode == "async"
                    else [(None, spec)])
            runs, missed = [], False
            for k_val, sub in subs:
                sub_cfg = (cfg if k_val is None else dataclasses.replace(
                    cfg, async_batch_k=int(k_val)))
                # the jitted vmapped program only depends on the
                # structural config (incl. async_batch_k), the grid SHAPE
                # (axis lengths fix the [n_cells] dim and, with a mesh,
                # the input shardings) and max_rounds — not the knob
                # values
                axes = sub.axes(sub_cfg)
                spec_shape = (tuple(len(v) for v in axes.values()),
                              sub.max_rounds)
                key = ("sweep", ex, self._structural_cfg(sub_cfg),
                       spec_shape, metric_fn, self.metric_name, mesh,
                       None if self._n_samples is None
                       else tuple(self._n_samples),
                       tele_spec)
                program = self._programs.get(key)
                if program is None:
                    missed = True
                    with obs_trace.span("session.compile", mode="sweep",
                                        n_cells=sub.n_cells):
                        program = make_sweep_program(
                            ex.model, ex.edge_data, ex.eval_set, sub_cfg,
                            sub, lr=ex.lr, batch=ex.batch,
                            n_samples=self._n_samples, metric_fn=metric_fn,
                            metric_name=self.metric_name,
                            mesh=mesh, telemetry=tele_spec)
                        self._cache_program(key, program)
                self._sweep_program, self._sweep_key = program, key
                runs.append((sub, sub_cfg, program))
            sp["cache"] = "miss" if missed else "hit"
        params_parts, out_parts = [], []
        for sub, sub_cfg, program in runs:
            with obs_trace.span("session.dispatch", mode="sweep",
                                n_cells=sub.n_cells):
                params, out = run_sweep_program(
                    program, self._initial_params(),
                    sub.cell_cfgs(sub_cfg))
            params_parts.append(params)
            out_parts.append(out)
        with obs_trace.span("session.report"):
            if len(out_parts) == 1:
                params, out = params_parts[0], out_parts[0]
            else:
                # async_batch_k is slowest-varying, so concatenating the
                # sub-sweeps along the cell axis reproduces spec.cells()
                params = jax.tree.map(
                    lambda *xs: jax.numpy.concatenate(xs, axis=0),
                    *params_parts)
                out = jax.tree.map(
                    lambda *xs: np.concatenate(xs, axis=0), *out_parts)
            report = SweepReport(
                spec=spec, axes=spec.axes(cfg), cells=spec.cells(cfg),
                out=out, policy=cfg.policy,
                elapsed_s=time.perf_counter() - t0, final_params=params)
        # workloads without a jittable metric (e.g. K-means F1) run the
        # program with NaN metric history; score the final params after it
        # (every cell in one call where the executor offers a batched
        # form) so the report's frontier still has an accuracy axis
        with obs_trace.span("session.evaluate") as sp:
            sp["n"], sp["batched"] = report.score_final_params(
                ex, self.metric_name)
        return report

    # -- AC-sync estimator plumbing -------------------------------------------

    @staticmethod
    def _update_ac(coord: CloudCoordinator, edge_params: List[Params],
                   prev_global: Params, new_global: Params,
                   tau: int) -> None:
        local_deltas = np.array([param_l2_delta(prev_global, p)
                                 for p in edge_params])
        global_delta = param_l2_delta(prev_global, new_global)
        coord.ac.update_estimates(local_deltas, global_delta, tau)
