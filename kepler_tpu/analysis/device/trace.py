"""Abstract tracing for the device tier: jaxpr + lowered-MLIR extraction.

Everything here runs WITHOUT devices or execution: programs are staged
with ``jit(...).trace(ShapeDtypeStruct...)`` (abstract shapes only) and
lowered to StableHLO text — no backend compile, no transfers, so the
whole tier completes on a CPU-only host (``JAX_PLATFORMS=cpu``) in
seconds. jax is imported lazily, pinned to the CPU platform with enough
virtual devices for the registry's meshes (the same
``xla_force_host_platform_device_count`` trick as tests/conftest.py).

What a trace yields (:class:`TraceReport`):

- the recursive **primitive histogram** of the jaxpr (sub-jaxprs of
  pjit/shard_map/scan/cond/pallas_call walked in), with version-noisy
  wrapper primitives (:data:`UNSTABLE_PRIMS`) excluded so fingerprints
  survive jax upgrades by design;
- the **collective set** (explicit communication primitives — the ones
  a ``shard_map`` schedule spells out; KTL122);
- **dtype-flow facts**: every half-precision ``convert_element_type``
  pair, every dot with a half-precision ACCUMULATOR (output dtype), and
  every reduction over half-precision operands (KTL120);
- **input/output aliasing** parsed from the lowered module's argument
  attributes: a donated argument XLA can alias carries
  ``tf.aliasing_output``; a donated-but-unaliasable one carries
  ``jax.buffer_donor`` (or nothing, plus a lower-time warning) — the
  silent perf cliff KTL121 exists to catch.
"""

from __future__ import annotations

import os
import re
import warnings
from dataclasses import dataclass, field
from typing import Any, Iterator, TYPE_CHECKING

if TYPE_CHECKING:  # registry imports stay import-light at runtime
    from kepler_tpu.analysis.device.registry import ProgramCase, ProgramSpec

#: explicit communication primitives a traced program can carry; the
#: KTL122 allowlists are spelled in these names
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum2", "ppermute", "pmax", "pmin", "all_to_all",
    "all_gather", "all_gather_invariant", "reduce_scatter", "pgather",
})

#: how the installed jax spells a collective under the varying-axes
#: checker → the name the allowlists use (``lax.psum`` inside a checked
#: ``shard_map`` traces as ``psum_invariant``)
COLLECTIVE_ALIASES = {"psum_invariant": "psum"}

#: wrapper/bookkeeping primitives whose counts are jax-version noise
#: (pjit nesting depth, replication-cast insertion); excluded from the
#: fingerprint histogram so the KTL123 ratchet pins PROGRAM structure,
#: not tracer internals
UNSTABLE_PRIMS = frozenset({
    "pjit", "jit", "closed_call", "core_call", "xla_call",
    "pbroadcast", "pvary", "custom_jvp_call", "custom_vjp_call",
    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr", "remat",
    "remat2", "checkpoint",
})

#: reductions whose OPERAND dtype must not be half precision
REDUCE_PRIMS = frozenset({
    "reduce_sum", "cumsum", "scatter-add", "add_any",
    "reduce_window_sum", "reduce_precision",
})

HALF_DTYPES = ("float16", "bfloat16")

_DONATION_WARNING = "donated buffers were not usable"


def ensure_cpu_devices(n_devices: int) -> Any:
    """Import jax pinned to a CPU host platform with ≥ ``n_devices``
    virtual devices and return the module.

    Must run before anything else initializes the jax backend in this
    process; if the backend is already up with too few devices (an
    embedding process that imported jax first), this raises instead of
    silently analyzing a differently-shaped mesh.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")
    have = len(jax.devices())
    if have < n_devices:
        raise RuntimeError(
            f"device tier needs {n_devices} virtual CPU devices, have "
            f"{have}; run in a fresh process (or set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8 before jax "
            f"imports)")
    return jax


@dataclass
class TraceReport:
    """Everything the KTL120-123 checks read about one traced case."""

    spec: "ProgramSpec"
    case: "ProgramCase"
    in_avals: tuple[str, ...] = ()
    out_avals: tuple[str, ...] = ()
    prim_counts: dict[str, int] = field(default_factory=dict)
    collectives: set[str] = field(default_factory=set)
    half_casts: dict[str, int] = field(default_factory=dict)
    half_dots: list[str] = field(default_factory=list)
    half_reduces: list[str] = field(default_factory=list)
    has_shard_map: bool = False
    arg_leaves: tuple[int, ...] = ()  # flat leaves per user-level arg
    aliased_args: set[int] = field(default_factory=set)  # flat indices
    donor_args: set[int] = field(default_factory=set)
    donation_warnings: list[str] = field(default_factory=list)

    @property
    def key(self) -> str:
        return f"{self.spec.name}/{self.case.name}"

    def flat_indices_of_arg(self, user_arg: int) -> set[int]:
        start = sum(self.arg_leaves[:user_arg])
        return set(range(start, start + self.arg_leaves[user_arg]))


def _sub_jaxprs(params: dict) -> Iterator[Any]:
    for value in params.values():
        items = value if isinstance(value, (list, tuple)) else (value,)
        for item in items:
            if hasattr(item, "eqns"):  # open Jaxpr
                yield item
            elif hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr  # ClosedJaxpr


def iter_eqns(jaxpr: Any) -> Iterator[Any]:
    """Every equation of ``jaxpr`` and its sub-jaxprs, depth-first."""
    for eqn in jaxpr.eqns:
        yield eqn
        yield from (e for sub in _sub_jaxprs(eqn.params)
                    for e in iter_eqns(sub))


def _aval_str(aval: Any) -> str:
    shape = ",".join(str(d) for d in getattr(aval, "shape", ()))
    dtype = getattr(aval, "dtype", None)
    return f"{getattr(dtype, 'name', dtype)}[{shape}]"


def _dtype_name(var: Any) -> str:
    dtype = getattr(getattr(var, "aval", None), "dtype", None)
    return getattr(dtype, "name", str(dtype))


def parse_main_arg_attrs(text: str) -> dict[int, dict[str, bool]]:
    """Per-argument aliasing attributes of the lowered ``@main``.

    → ``{flat_arg_index: {"aliased": bool, "donor": bool}}``. The
    signature is located as the lines from ``func.func public @main(``
    up to the body-opening brace. An attribute dict is read by brace
    matching, not by a regex: it may embed quoted strings that contain
    braces (``mhlo.sharding = "{devices=[8,1]<=[8]}"``) and, under
    Shardy, nested braces of its own
    (``sdy.sharding = #sdy.sharding<@mesh, [{"node"}, {}]>``).
    """
    start = text.find("func.func public @main(")
    if start < 0:
        start = text.find("func.func @main(")
    if start < 0:
        return {}
    sig_lines: list[str] = []
    for line in text[start:].splitlines():
        sig_lines.append(line)
        if line.rstrip().endswith("{"):
            break
    sig = " ".join(sig_lines)
    out: dict[int, dict[str, bool]] = {}
    for m in re.finditer(r'%arg(\d+):\s*tensor<[^>]*>\s*', sig):
        attrs = _attr_dict_at(sig, m.end())
        out[int(m.group(1))] = {
            "aliased": "tf.aliasing_output" in attrs,
            "donor": "jax.buffer_donor" in attrs,
        }
    return out


def _attr_dict_at(sig: str, pos: int) -> str:
    """The balanced ``{...}`` starting at ``sig[pos]`` ("" when the
    argument carries no attribute dict). Braces inside double-quoted
    strings do not count."""
    if pos >= len(sig) or sig[pos] != "{":
        return ""
    depth = 0
    quoted = False
    for i in range(pos, len(sig)):
        ch = sig[i]
        if ch == '"':
            quoted = not quoted
        elif not quoted and ch == "{":
            depth += 1
        elif not quoted and ch == "}":
            depth -= 1
            if depth == 0:
                return sig[pos:i + 1]
    return sig[pos:]  # unbalanced: the rest of the signature


def trace_case(spec: "ProgramSpec", case: "ProgramCase") -> TraceReport:
    """Stage one registry case abstractly and extract its report."""
    jax = ensure_cpu_devices(spec.n_devices)
    fn, avals = spec.build(case)
    traced = fn.trace(*avals)
    closed = traced.jaxpr
    report = TraceReport(spec=spec, case=case)
    report.in_avals = tuple(_aval_str(v.aval)
                            for v in closed.jaxpr.invars)
    report.out_avals = tuple(_aval_str(v.aval)
                             for v in closed.jaxpr.outvars)
    report.arg_leaves = tuple(len(jax.tree.leaves(a)) for a in avals)
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name not in UNSTABLE_PRIMS:
            report.prim_counts[name] = report.prim_counts.get(name, 0) + 1
        if name == "shard_map":
            report.has_shard_map = True
        collective = COLLECTIVE_ALIASES.get(name, name)
        if collective in COLLECTIVE_PRIMS:
            report.collectives.add(collective)
        elif name == "convert_element_type":
            src, dst = _dtype_name(eqn.invars[0]), _dtype_name(eqn.outvars[0])
            if src in HALF_DTYPES or dst in HALF_DTYPES:
                pair = f"{src}->{dst}"
                report.half_casts[pair] = report.half_casts.get(pair, 0) + 1
        elif name == "dot_general":
            out_dt = _dtype_name(eqn.outvars[0])
            if out_dt in HALF_DTYPES:
                operands = "/".join(_dtype_name(v) for v in eqn.invars)
                report.half_dots.append(f"{operands} -> {out_dt}")
        elif name in REDUCE_PRIMS:
            op_dt = _dtype_name(eqn.invars[0])
            if op_dt in HALF_DTYPES:
                report.half_reduces.append(f"{name}({op_dt})")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        text = traced.lower().as_text()
    for w in caught:
        if _DONATION_WARNING in str(w.message):
            report.donation_warnings.append(str(w.message))
    for idx, attrs in parse_main_arg_attrs(text).items():
        if attrs["aliased"]:
            report.aliased_args.add(idx)
        elif attrs["donor"]:
            report.donor_args.add(idx)
    return report


def fingerprint(report: TraceReport) -> dict:
    """Normalized structural fingerprint for the KTL123 ratchet.

    Built only from facts that are stable across jax versions by
    design: user-visible aval signatures, the histogram of REAL
    compute/data-movement primitives (:data:`UNSTABLE_PRIMS` excluded),
    the explicit collective set, half-precision cast pairs, shard_map
    presence, and which flat args alias their outputs.
    """
    return {
        "in_avals": list(report.in_avals),
        "out_avals": list(report.out_avals),
        "primitives": dict(sorted(report.prim_counts.items())),
        "collectives": sorted(report.collectives),
        "half_casts": dict(sorted(report.half_casts.items())),
        "shard_map": report.has_shard_map,
        "donated_args": sorted(report.aliased_args | report.donor_args),
    }
