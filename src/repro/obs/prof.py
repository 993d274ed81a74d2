"""Program profiles: XLA cost/memory/collective introspection.

One compiled EL program = one :class:`ProgramProfile` — FLOPs and bytes
accessed from XLA's ``cost_analysis()``, per-device argument / output /
temp / alias bytes (and the derived peak) from ``memory_analysis()``,
and a collective census parsed from the optimized HLO.  The profile is
the static half of observability: the telemetry rings (``repro.obs.
rings``) say what a run *did*, the profile says what the executable
*is* — how many all-gathers a sharded program issues per dispatch,
whether donation actually aliased the params, how much live memory the
while-loop body holds.

Extraction is an extra ``lower().compile()`` (AOT compiles do not share
the jit dispatch cache), so callers keep it lazy and opt-in:
``ELSession`` computes a profile once per cached program only when
asked (``profile=``/``contract=`` or ``REPRO_EL_PROFILE=1``), and
``scripts/bench_el.py`` profiles every tier it times anyway.

:class:`CollectiveContract` turns the profile into a declarative,
dispatch-time assertion — "a sharded sync program all-gathers and never
all-reduces", "a donated program aliases exactly the param bytes" —
replacing one-off HLO string checks in tests with a single checkable
object (``contract.enforce(profile)`` raises
:class:`ContractViolation`).

The HLO collective parser (:func:`parse_collectives` /
:func:`_type_bytes`) moved here from ``repro.launch.dryrun`` — dryrun
mutates ``XLA_FLAGS`` at import (512 forced devices), so nothing
observability-side may import it; dryrun now re-exports from here.
``repro.obs`` never imports ``repro.el``.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Mapping, Optional, Tuple, Union

#: collective op mnemonics the census meters (HLO op-name spellings)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|"
                       r"u64|c64|c128)\[([0-9,]*)\]")

_CUSTOM_CALL_RE = re.compile(r'custom_call_target="([^"]+)"')

#: ``%x = <result type> <op>(`` — the type may carry a TPU tiled layout
#: (``f32[16,8]{1,0:T(8,128)S(1)}``) but never an ``=``
_COLLECTIVE_RE = re.compile(
    r"=\s+([^=]+?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")


def _type_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, Any]:
    """Sum the bytes moved by every collective op in the optimized HLO.

    Post-optimization HLO prints operands without types, so we meter the
    RESULT type of each collective: for all-reduce / all-to-all /
    collective-permute the result equals the operand; for all-gather the
    result is the gathered (received) payload per device; for
    reduce-scatter we scale the result back up by the shrink factor when
    derivable.  Shapes in the partitioned module are per-device.
    ``-start`` async forms are counted once (the ``-done`` op has a
    different result structure and is skipped via the op-name match).
    """
    per_op: Dict[str, Dict[str, float]] = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        result_type, op = m.group(1), m.group(2)
        nbytes = _type_bytes(result_type)
        d = per_op.setdefault(op, {"count": 0, "bytes": 0})
        d["count"] += 1
        d["bytes"] += nbytes
    total = sum(d["bytes"] for d in per_op.values())
    return {"per_op": per_op, "bytes_per_device": total}


def parse_custom_calls(hlo_text: str) -> Dict[str, int]:
    """Count the optimized HLO's custom calls by target — a natively
    compiled Pallas TPU kernel shows up as ``tpu_custom_call``; an
    interpreted one leaves none."""
    counts: Dict[str, int] = {}
    for target in _CUSTOM_CALL_RE.findall(hlo_text):
        counts[target] = counts.get(target, 0) + 1
    return counts


# ---------------------------------------------------------------------------
# Compiled-artifact readers (best-effort per section)
# ---------------------------------------------------------------------------


def memory_dict(compiled) -> Dict[str, Any]:
    """``memory_analysis()`` of a Compiled as a plain dict (``{"error":
    ...}`` when the backend cannot report it)."""
    try:
        ma = compiled.memory_analysis()
    except Exception as e:                                  # pragma: no cover
        return {"error": str(e)}
    out: Dict[str, Any] = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def cost_dict(compiled) -> Dict[str, Any]:
    """``cost_analysis()`` of a Compiled, filtered to the stable keys."""
    try:
        ca = compiled.cost_analysis()
    except Exception as e:                                  # pragma: no cover
        return {"error": str(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {k: float(v) for k, v in ca.items()
            if k in ("flops", "bytes accessed", "transcendentals")}


# ---------------------------------------------------------------------------
# ProgramProfile
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ProgramProfile:
    """The static cost card of one compiled XLA executable.

    All fields are best-effort (``None`` when the backend withholds the
    analysis); ``collectives`` maps op mnemonic → ``{"count", "bytes"}``
    with per-device result bytes (see :func:`parse_collectives`), and
    ``custom_calls`` maps custom-call target → count (Pallas kernels).
    ``peak_live_bytes`` is the bench convention: arguments + outputs +
    temps − aliased, per device.
    """

    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    transcendentals: Optional[float] = None
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    alias_bytes: Optional[int] = None
    generated_code_bytes: Optional[int] = None
    peak_live_bytes: Optional[int] = None
    collectives: Dict[str, Dict[str, float]] = \
        dataclasses.field(default_factory=dict)
    collective_bytes: int = 0
    custom_calls: Dict[str, int] = dataclasses.field(default_factory=dict)
    hlo_lines: Optional[int] = None
    backend: Optional[str] = None
    donated: bool = False
    errors: Tuple[str, ...] = ()

    def collective_count(self, op: str) -> int:
        """Census count of one collective op (0 when absent)."""
        return int(self.collectives.get(op, {}).get("count", 0))

    @property
    def total_collectives(self) -> int:
        return sum(int(d.get("count", 0))
                   for d in self.collectives.values())

    def to_json(self) -> Dict[str, Any]:
        """A JSON-safe snapshot (``ELReport.telemetry["profile"]``,
        BENCH rows)."""
        d = dataclasses.asdict(self)
        d["errors"] = list(self.errors)
        return d

    def summary(self) -> str:
        """One human line: flops, peak bytes, census."""
        cens = ", ".join(f"{op}={self.collective_count(op)}"
                         for op in COLLECTIVES
                         if self.collective_count(op)) or "none"
        flops = "?" if self.flops is None else f"{self.flops:.3g}"
        peak = ("?" if self.peak_live_bytes is None
                else f"{self.peak_live_bytes / 1e6:.2f}MB")
        return (f"flops={flops} peak={peak} alias={self.alias_bytes} "
                f"collectives[{cens}]")


def profile_compiled(compiled, *, donated: bool = False) -> ProgramProfile:
    """Extract a :class:`ProgramProfile` from a ``jax`` Compiled object
    (the result of ``jit(f).lower(*args).compile()``).  Every section is
    best-effort: a backend that withholds one analysis still yields a
    profile, with the failure recorded in ``profile.errors``."""
    errors: List[str] = []
    kw: Dict[str, Any] = {"donated": donated}

    cost = cost_dict(compiled)
    if "error" in cost:
        errors.append(f"cost: {cost['error']}")
    else:
        kw["flops"] = cost.get("flops")
        kw["bytes_accessed"] = cost.get("bytes accessed")
        kw["transcendentals"] = cost.get("transcendentals")

    mem = memory_dict(compiled)
    if "error" in mem:
        errors.append(f"memory: {mem['error']}")
    else:
        kw["argument_bytes"] = mem.get("argument_size_in_bytes")
        kw["output_bytes"] = mem.get("output_size_in_bytes")
        kw["temp_bytes"] = mem.get("temp_size_in_bytes")
        kw["alias_bytes"] = mem.get("alias_size_in_bytes")
        kw["generated_code_bytes"] = mem.get(
            "generated_code_size_in_bytes")
        if None not in (kw.get("argument_bytes"), kw.get("output_bytes"),
                        kw.get("temp_bytes"), kw.get("alias_bytes")):
            kw["peak_live_bytes"] = (kw["argument_bytes"]
                                     + kw["output_bytes"]
                                     + kw["temp_bytes"]
                                     - kw["alias_bytes"])

    try:
        hlo = compiled.as_text()
        census = parse_collectives(hlo)
        kw["collectives"] = census["per_op"]
        kw["collective_bytes"] = int(census["bytes_per_device"])
        kw["custom_calls"] = parse_custom_calls(hlo)
        kw["hlo_lines"] = hlo.count("\n")
    except Exception as e:                                  # pragma: no cover
        errors.append(f"hlo: {e}")

    try:
        import jax
        kw["backend"] = jax.default_backend()
    except Exception:                                       # pragma: no cover
        pass
    return ProgramProfile(errors=tuple(errors), **kw)


def profile_jit(jfn, *example_args, donated: bool = False
                ) -> ProgramProfile:
    """Profile a jitted callable by AOT-lowering it on ``example_args``
    (concrete arrays or ``ShapeDtypeStruct`` trees).

    The AOT compile does NOT share the jit dispatch cache — it costs one
    extra XLA compile — so callers cache the result per program (the
    session stores it on the :class:`repro.el.cache.ProgramCache`
    entry).  ``donated`` is a caller annotation recorded on the profile
    (the aliasing itself is read from ``memory_analysis``)."""
    compiled = jfn.lower(*example_args).compile()
    return profile_compiled(compiled, donated=donated)


# ---------------------------------------------------------------------------
# Collective contracts
# ---------------------------------------------------------------------------


class ContractViolation(AssertionError):
    """A compiled program broke its declared collective/aliasing
    contract."""


#: a count constraint: an exact int or an inclusive ``(lo, hi)`` range
CountConstraint = Union[int, Tuple[int, int]]


def _check_count(op: str, actual: int, want: CountConstraint
                 ) -> Optional[str]:
    if isinstance(want, tuple):
        lo, hi = want
        if not (lo <= actual <= hi):
            return (f"{op}: count {actual} outside [{lo}, {hi}]")
        return None
    if actual != int(want):
        return f"{op}: count {actual} != {int(want)}"
    return None


@dataclasses.dataclass(frozen=True)
class CollectiveContract:
    """A declarative assertion over a :class:`ProgramProfile`.

    ``counts`` maps collective op mnemonics to an exact count or an
    inclusive ``(lo, hi)`` range; ops NOT named are unconstrained.
    ``alias_bytes`` (when set) must match the profile exactly — the
    donation contract is ``alias_bytes == param_bytes`` for donated
    programs and ``== 0`` otherwise.  ``check`` returns the violations
    (empty = pass); ``enforce`` raises :class:`ContractViolation`.

    The canonical instances::

        # sync-sharded on the 2x2 debug mesh: gather-before-reduce —
        # the edge stack is all-gathered BEFORE the aggregation einsum,
        # so the program must contain NO all-reduce (any partial-sum
        # reordering would break sharded-vs-unsharded bit-identity)
        CollectiveContract("sync-sharded-2x2",
                           counts={"all-gather": (1, 16),
                                   "all-reduce": 0})

        # donated run: XLA aliased the whole param tree into the output
        CollectiveContract("donated", alias_bytes=1920)
    """

    name: str = "contract"
    counts: Mapping[str, CountConstraint] = \
        dataclasses.field(default_factory=dict)
    alias_bytes: Optional[int] = None

    def check(self, profile: ProgramProfile) -> List[str]:
        """The list of violations (empty when the profile satisfies the
        contract).  A profile that could not read part of the executable
        (``profile.errors``) violates every contract: a census it never
        took would otherwise read as zero collectives."""
        bad: List[str] = [f"profile incomplete: {e}"
                          for e in profile.errors]
        for op, want in sorted(dict(self.counts).items()):
            msg = _check_count(op, profile.collective_count(op), want)
            if msg is not None:
                bad.append(msg)
        if self.alias_bytes is not None:
            actual = profile.alias_bytes
            if actual is None:
                bad.append("alias_bytes: unavailable "
                           "(memory_analysis withheld)")
            elif int(actual) != int(self.alias_bytes):
                bad.append(f"alias_bytes: {actual} != {self.alias_bytes}")
        return bad

    def enforce(self, profile: ProgramProfile) -> None:
        bad = self.check(profile)
        if bad:
            raise ContractViolation(
                f"contract {self.name!r} violated: " + "; ".join(bad))


def param_tree_bytes(tree: Any) -> int:
    """Per-device bytes of a params tree — the donated side of the alias
    contract.  A placed ``jax.Array`` counts its first shard's on-device
    size, layout padding included (a TPU stores f32 ``[59, 8]`` in whole
    tiles, and ``memory_analysis`` reports aliased bytes the same way);
    anything else (numpy, ``ShapeDtypeStruct``) counts shape x itemsize.
    """
    import jax
    import numpy as np
    total = 0
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            total += int(leaf.addressable_data(0).on_device_size_in_bytes())
        else:
            total += int(np.prod(np.shape(leaf), dtype=np.int64)
                         * np.dtype(leaf.dtype).itemsize)
    return total


#: loose all-gather bound for multi-device contracts: the exact count is
#: an XLA-version detail (the optimizer merges gathers between releases;
#: this toolchain emits 2 per single-run program where older ones emitted
#: 6) — the INVARIANT is >= 1 gather and 0 all-reduces.
DEFAULT_GATHER_RANGE: Tuple[int, int] = (1, 16)


#: all-reduce allowance for sharded SCENARIO programs: the churn mask
#: arithmetic (active-edge counts, mask-renormalized weight sums,
#: slowest-ACTIVE-edge slot) reduces over the sharded edge axis, which
#: GSPMD lowers as partial-sum all-reduces.  These are scalar
#: control-plane reductions, not data-plane partial sums — the
#: gather-before-reduce discipline still governs the parameter path.
SCENARIO_REDUCE_RANGE: Tuple[int, int] = (0, 32)


#: collective range of a language model's sync round on a mesh: an
#: all-to-all and an all-gather for each parameter leaf where the edges
#: tile the mesh (XLA may split a leaf's into several), none where every
#: chip runs every edge
EXCHANGE_RANGE: Tuple[int, int] = (0, 4096)


def default_contract(*, mesh=None, donated: bool = False,
                     param_bytes: Optional[int] = None,
                     mode: str = "sync",
                     scenario: bool = False,
                     exchange: bool = False) -> CollectiveContract:
    """The contract every compiled EL program is expected to satisfy.

    * no mesh (or a 1-device mesh): NO collectives of any kind;
    * multi-device mesh (sync AND async): gather-before-reduce — at
      least one all-gather, zero all-reduce / reduce-scatter /
      all-to-all (bit-identity with the unsharded program forbids
      partial-sum reordering);
    * ``scenario`` (a ``ScenarioSpec``-path program) on a multi-device
      mesh: additionally up to ``SCENARIO_REDUCE_RANGE[1]`` all-reduces
      — the scalar churn-mask reductions over the sharded edge axis;
    * ``exchange`` (a language model's sync round,
      ``repro.el.ingraph.make_token_round``) on a multi-device mesh: the
      edge stack never leaves its chips — all-to-alls and all-gathers of
      the per-leaf means (``EXCHANGE_RANGE`` each), still no all-reduce
      or reduce-scatter (the mean's order is the unsharded one);
    * ``donated`` with ``param_bytes``: the whole param tree aliased
      (``alias_bytes == param_bytes``); non-donated: ``== 0``.
    """
    n_dev = 1
    if mesh is not None:
        import numpy as np
        n_dev = int(np.asarray(mesh.devices).size)
    if n_dev > 1 and exchange:
        counts: Dict[str, CountConstraint] = {
            "all-gather": EXCHANGE_RANGE, "all-to-all": EXCHANGE_RANGE,
            "all-reduce": 0, "reduce-scatter": 0}
    elif n_dev > 1:
        counts = {
            "all-gather": DEFAULT_GATHER_RANGE,
            "all-reduce": (SCENARIO_REDUCE_RANGE if scenario else 0),
            "reduce-scatter": 0, "all-to-all": 0}
    else:
        counts = {op: 0 for op in COLLECTIVES}
    alias = None
    if donated and param_bytes is not None:
        alias = int(param_bytes)
    elif not donated:
        alias = 0
    tag = "sharded" if n_dev > 1 else "replicated"
    if exchange:
        tag += "-exchange"
    if scenario:
        tag += "-scenario"
    return CollectiveContract(
        name=f"{mode}-{tag}" + ("-donated" if donated else ""),
        counts=counts, alias_bytes=alias)
