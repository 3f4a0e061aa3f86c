#!/usr/bin/env python3
"""Generate ``docs/developer/static-analysis.md`` from the keplint registry.

Same pattern (and teeth) as ``hack/gen_config_docs.py`` /
``gen_metric_docs.py``: the rule catalog is rendered from the live
registry in ``kepler_tpu.analysis``, so the doc can never silently drift
from the rules — adding a rule without regenerating fails ``--check``
(and the freshness test), and every rule must carry a summary and a
rationale or the generator refuses to render.

Usage:  python hack/gen_lint_docs.py [--check]
  --check   exit 1 if docs/developer/static-analysis.md is stale.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from kepler_tpu.analysis import all_rules  # noqa: E402

OUT_PATH = os.path.join(REPO, "docs", "developer", "static-analysis.md")

PREAMBLE = """\
# Static analysis: keplint + the typing ratchet

Generated from the live rule registry by `hack/gen_lint_docs.py` — do
not edit by hand; regenerate with `python hack/gen_lint_docs.py` (CI
checks freshness with `--check`).

The attribution formula is only correct while a handful of code-level
invariants hold *everywhere*: counter deltas must be wrap-aware, timing
logic must use monotonic clocks, published snapshots must stay
immutable, jitted kernels must stay pure, lock and input-hygiene
contracts must survive helper-function hops. Generic linters cannot
see those — they are domain invariants — so `keplint`
(`kepler_tpu/analysis/`) encodes each one as an AST check. `make lint`
runs keplint, ruff (config committed in `pyproject.toml`), and mypy
(per-module strictness ratchet, also in `pyproject.toml`).

## Running

```
python -m kepler_tpu.analysis              # lint kepler_tpu/, hack/, benchmarks/
python -m kepler_tpu.analysis path/ file.py
python -m kepler_tpu.analysis --list-rules
python -m kepler_tpu.analysis --format=sarif   # SARIF 2.1.0 (make keplint-sarif)
python -m kepler_tpu.analysis --per-file       # disable cross-module analysis
python -m kepler_tpu.analysis --device-tier    # + trace device programs (KTL120-123)
python -m kepler_tpu.analysis --protocol-tier  # + explore protocol models (KTL130-132)
python -m kepler_tpu.analysis --only=KTL120    # single-rule iteration loop
```

Exit codes: `0` clean (baselined findings tolerated), `1` new
violations, `2` usage errors. `--format=json|sarif` emits
machine-readable reports (SARIF 2.1.0 minimal profile, consumable as
CI annotations). `--only=KTLxxx[,KTLxxx]` restricts a run to the named
rules so a single-rule iteration loop does not pay every family's cost
— in particular the device tier's trace cost. Naming a KTL12x id in
`--only` implies `--device-tier`; a KTL130-132 id implies
`--protocol-tier`.

## Whole-program analysis

KTL101-110 run per file. KTL111-113 run once per lint over a
`ProjectContext` (`kepler_tpu/analysis/project.py`): every file is
parsed **once** per run and shared by all rules, then a module-level
symbol table, light type inference (constructor assignments, parameter
annotations), and a **call graph** link resolved call sites across
modules. On top of the graph:

- **Thread roles** propagate from declared roots along call edges:
  `# keplint: thread-role=<role>` on a `def` or `class` names a root
  (agent thread, `_FetchWorker`, shutdown paths, HTTP handlers); the
  `hot-loop` marker roots the `hot-loop` role; and callables passed to
  a `# keplint: role-registrar=<role>` function (`APIServer.register`)
  become roots of that role. Propagation stops at `# keplint:
  role-boundary` seams — the meter/informer/persistence functions that
  do I/O *by design* and keep their own contracts.
- **Lock summaries** record which locks each function acquires
  (directly and through its call closure), feeding the KTL111
  lock-order graph; lock identity is hoisted to the class that
  constructs the lock, so cross-module acquisitions alias correctly.
- **Taint** (KTL112) flows from sources (`# keplint: taint-source`
  functions like `peek_node_name`; `.headers`/`.path`/`.body` reads in
  `http-handler`-role functions) through assignments and resolved call
  edges until a sanitizer launders it: a function marked `# keplint:
  sanitizes` (the registry: `wire.sanitize_node_name`,
  `wire.decode_report`, `server.http.printable`) or a built-in
  coercion (`int`, `float`, …). Sinks: Prometheus label values, keys
  of object-attached stores, sequence indexes, log-call arguments, and
  `# keplint: taint-sink` functions.

`--per-file` restricts KTL111-113 to one-file contexts (no cross-module
call graph) — useful for bisecting which findings are genuinely
interprocedural; the test suite uses it to prove the call graph is
load-bearing.

## Device tier (kepljax, KTL120-123)

The host tiers see source text; the compiled packed/sharded fleet
programs the attribution math actually runs on are a different plane.
`--device-tier` (wired into `make lint`) traces every entry of a
declarative **program registry**
(`kepler_tpu/analysis/device/registry.py`) abstractly —
`jit(...).trace(ShapeDtypeStruct...)` + StableHLO lowering on a
CPU-only host (`JAX_PLATFORMS=cpu`, virtual devices, no execution, no
backend compile) — and runs four check families over the jaxprs:

- **KTL120 dtype-flow** — no f16/bf16 dot accumulators or reduction
  operands anywhere; half casts only at the boundaries the entry
  declares (`allowed_half_casts`, e.g. the packed program's one
  `float32->float16` wire quantizer, bf16 MXU operand feeds).
- **KTL121 donation-alias** — the entry's `donates` contract must be
  realized in the lowered module's argument attributes
  (`tf.aliasing_output` / `jax.buffer_donor`), and no undeclared arg
  may alias; a dropped donation is a silent full-copy per window.
- **KTL122 collective-discipline** — the traced program's explicit
  collectives must stay inside `allowed_collectives`, and
  `require_shard_map` entries must actually contain a `shard_map`
  (GSPMD inserts collectives at partitioning time, invisible to the
  jaxpr tier — losing the shard_map is how a regression to a
  replicated-index gather reads here).
- **KTL123 program-ratchet** — a normalized structural fingerprint per
  entry/case (aval signatures, compute-primitive histogram with
  version-noisy wrapper primitives excluded, collective set, half-cast
  pairs, shard_map presence, donation map) is committed as a golden
  snapshot in `.kepljax.json`; drift fails lint with a field diff.
  After an INTENDED program change, `make kepljax-snapshots`
  regenerates and the snapshot diff becomes part of code review.

Registry entries are declarative: factory + representative bucket-shape
cases (including pad-row/minimal-ladder edges) + the contract
vocabulary above (`donates`, `allowed_collectives`,
`allowed_half_casts`, `require_shard_map`, `n_devices`). CPU-host
caveats: traces stage the CPU lowering of each program (the packed
program serves f32 estimator compute off-TPU by design, so bf16-only
TPU casts do not appear), and fingerprints describe structure, not
cost. A jax upgrade can legitimately shift a fingerprint; regenerating
snapshots then is expected and the diff shows the cause.

## Protocol tier (kepmc, KTL130-133)

The host tiers read source; the device tier reads jaxprs; neither can
see an *ordering* bug — a safety violation that only a specific
interleaving of deliveries, crashes, restarts and scale events
produces (PR 16 shipped three of them). `--protocol-tier` (wired into
`make lint`; `make protocheck` runs it alone) runs **kepmc**
(`kepler_tpu/analysis/protocol/`): an explicit-state model checker
that exhaustively explores every reachable interleaving of a small
fleet and checks safety invariants in every state.

The models are thin adapters, not re-implementations: each transition
calls the SAME pure decision functions production runs —
`plan_membership_apply`/`CoordinatorLease.adopt`/`plan_succession`
(`fleet/membership.py`), `SeqTracker.observe`/`seed_fresh_tracker`/
`reseed_on_ownership_return`/`keyframe_wanted`/`delta_base_matches`/
`plan_ack_cursor`/`plan_rewind_tail` (`fleet/delivery.py`,
`fleet/spool.py`). A model bug is possible; a model/production *drift*
requires changing a shared function both see. KTL133 (below) fences
the other direction: protocol state may not move outside those
functions.

Specs are declarative registry entries
(`kepler_tpu/analysis/protocol/registry.py`), mirroring the device
tier's `ProgramSpec` shape: a `ProtocolSpec` names the model factory,
the production source module its transitions drive, the invariants to
check, and bounded `ProtocolCase`s (2-3 replicas, 1-2 agents, a
handful of windows/epochs — the scope where these protocols' bugs
live, small enough for exhaustive BFS in seconds). Each case carries a
`max_states` ceiling; blowing it raises `StateExplosionError` — lint
FAILS rather than silently truncating the search.

Event vocabulary (per model, composed from): message `deliver` /
`duplicate` / reorder (messages persist in the state, so any delivery
order is explored), dropped responses, `crash` / `restart`, `leave` /
join succession, false-`suspect` probing, `rewind` / replay,
ownership `scale` swaps, keyframe/delta sends with loss and `409`
responses, base-row eviction.

- **KTL130 protocol-epoch-safety** — lease/membership: at most one
  self-believed holder per epoch (crash-heal scope), the holder is a
  member of its own peer set, epochs stay contiguous (no skipped or
  double-minted bumps), and no replica wedges awaiting a transfer that
  can never arrive.
- **KTL131 protocol-loss-accounting** — delivery/spool: no reachable
  schedule fabricates loss (counts a delivered window as lost), the
  spool ack cursor never skips an unsent record, stale acks are
  rejected, rewinds stay bounded to already-acked tails.
- **KTL132 protocol-replay-idempotence** — replayed windows are
  duplicates, never loss; after a 409 the next send is always a
  keyframe (the needs-keyframe loop converges in one round-trip);
  duplicate keyframes still plant the delta base.

A violation prints as a **counterexample**: the minimal event trace
(BFS guarantees shortest-path) from the initial state to the violating
state, one event per line, ending with the violated invariant and the
state that broke it. Read it top-down as a schedule — each line is one
atomic event the fleet could execute in that order; reproduce it by
replaying the same calls against the real objects (the pinned
regression tests in `tests/test_protocol.py` do exactly that). The
committed baseline stays empty for this tier too: a counterexample on
the shipped tree is a bug to fix, never to grandfather.

KTL133 (`protocol-transition-marker`) is the lexical fence that keeps
the tier honest: inside `kepler_tpu/fleet/`, assignments to protocol
state attributes (lease epoch/holder, ring epoch, seq watermarks,
spool cursor, keyframe base rows) are only legal inside functions
marked `# keplint: protocol-transition`. An unmarked write is exactly
a transition the checker does not know about. It is an ordinary
per-file rule and always runs.

## Suppressing

Append `# keplint: disable=KTL1xx` to the offending line (or put it on
a comment line directly above); several ids separate with commas, and a
bare `disable` suppresses every rule on that line. `# keplint:
disable-file=KTL1xx` anywhere in the file suppresses a rule file-wide.
Every suppression should say *why* in the surrounding comment.
Suppression applies to whole-program rules too: the directive lives in
the file where the diagnostic lands.

## Annotation vocabulary

Rules that need to know which code is special read declarative markers
instead of hardcoding module lists:

| Marker | Meaning |
| --- | --- |
| `# keplint: monotonic-only` (file-level) | KTL101: this module's timing math must never call the wall clock directly |
| `# keplint: hot-loop` (above a `def`) | KTL106/KTL113: this function runs on the monitor refresh path; no sleeps/blocking I/O, lexically or via any call chain |
| `# keplint: guarded-by=_lock` (on an attribute assignment in `__init__`) | KTL108/KTL111: writes to this attribute require `with self._lock` (KTL111 checks writers in other classes/modules too) |
| `# keplint: requires-lock=_lock` (above a `def`) | KTL108/KTL111: this function may only be called with the lock held; callers are checked, cross-module included |
| `# keplint: donates=<positions>` (on a callable binding) | KTL110: calls through this binding consume the arguments at those positions |
| `# keplint: layout-definition` (above a `def`/`class`) | KTL114: the one scope allowed to spell packed row-layout offset arithmetic |
| `# keplint: thread-role=<role>` (above a `def` or `class`) | KTL113: roots the thread role here; it propagates to everything reachable |
| `# keplint: role-registrar=<role>` (above a `def`) | KTL113: callables passed to this function become roots of `<role>` |
| `# keplint: role-boundary` (above a `def`) | KTL113: role propagation stops here — the seam keeps its own contract |
| `# keplint: forbid-role=<role>` (above a `class`) | KTL113: functions running under `<role>` may not call this class's methods |
| `# keplint: allow-role=<role>` (above a `def`) | KTL113: sanctioned exception to the enclosing class's `forbid-role` |
| `# keplint: taint-source` (above a `def`) | KTL112: this function's return value is untrusted input |
| `# keplint: sanitizes` (above a `def`) | KTL112: passing a value through this function launders its taint |
| `# keplint: taint-sink[=label]` (above a `def`) | KTL112: tainted arguments to this function are findings |
| `# keplint: protocol-transition` (above a `def`) | KTL133: this function is a declared protocol transition — the one place protocol state attributes may be written (and the kepmc models cover it) |

## Baseline ratchet

`.keplint.json` at the repo root freezes pre-existing violation counts
per `path::rule`. New violations fail; baselined ones pass; *fixed*
ones surface as stale entries — regenerate with
`python -m kepler_tpu.analysis --write-baseline` to ratchet the ceiling
down. The committed baseline is **empty**: every finding in the shipped
tree was fixed, not grandfathered (`tests/test_keplint.py` pins this —
including for the whole-program rules).

The device tier has its own ratchet shape: the committed
`.kepljax.json` golden fingerprints (see above) — drift fails, and
regeneration is an explicit, reviewable act.

The same ratchet stance applies to typing: `pyproject.toml` declares a
strict mypy tier (`config/`, `monitor/snapshot`, `fleet/wire`,
`fleet/window`, `fleet/scoreboard`, `fleet/aggregator`,
`fleet/membership`, `fleet/delivery`, `fault/`, `analysis/` (the
protocol tier included), `parallel/packed`, `parallel/mesh`,
`utils/jaxenv` — fully typed, `disallow_untyped_defs`) and a
checked tier (`monitor/`, `fleet/`, `service/` —
`check_untyped_defs`); modules move *up* tiers, never down.

## Extending

Per-file rules subclass `kepler_tpu.analysis.Rule` and implement
`check(ctx)` over the shared `FileContext` (use `ctx.walk_nodes`, the
once-per-run node list, instead of re-walking `ctx.tree`).
Whole-program rules subclass `ProjectRule` and implement
`check_project(project)` over the `ProjectContext` (symbol table, call
graph, roles, lock summaries). Device-tier rules subclass `DeviceRule`
and implement `check_trace(report)` over a
`kepler_tpu.analysis.device.trace.TraceReport`; new device programs
register a `ProgramSpec` (factory + cases + contract) in
`kepler_tpu/analysis/device/registry.py` and commit regenerated
snapshots. Protocol-tier rules subclass `ProtocolRule` and implement
`check_model(report)` over a
`kepler_tpu.analysis.protocol.ModelReport` (the spec, the case, the
exploration result with its counterexamples); new protocol machines
register a `ProtocolSpec` (model factory + bounded cases +
invariants) in `kepler_tpu/analysis/protocol/registry.py`, drive REAL
pure functions from `kepler_tpu/fleet/` in their transitions, and
mark those functions `# keplint: protocol-transition` so KTL133 keeps
the write surface closed. Either way: set `id`/`name`/`severity`/`summary`/
`rationale` (and `tree_scope` if the rule polices `hack/` or
`benchmarks/` too), decorate with `@register`, add a good/bad fixture
pair to `tests/test_keplint.py` (cross-module fixtures for project
rules, spec fixtures in `tests/test_kepljax.py` for device rules), and
regenerate this doc. Engine internals (directives, baselines, file
walking, SARIF) live in `kepler_tpu/analysis/engine.py` and
`__main__.py`.

## Rule catalog
"""


def render() -> str:
    rules = all_rules()
    missing = [r.id for r in rules if not (r.summary and r.rationale)]
    if missing:
        raise SystemExit(
            f"gen_lint_docs: rules missing summary/rationale: {missing}")
    from kepler_tpu.analysis import ProjectRule
    from kepler_tpu.analysis.engine import DeviceRule, ProtocolRule

    lines = [PREAMBLE]
    lines.append("| Rule | Name | Tier | Scope | Severity | Invariant |")
    lines.append("| --- | --- | --- | --- | --- | --- |")
    for r in rules:
        if isinstance(r, ProtocolRule):
            tier, scope = "protocol", "explored protocol models"
        elif isinstance(r, DeviceRule):
            tier, scope = "device", "traced device programs"
        elif isinstance(r, ProjectRule):
            tier = "whole-program"
            scope = ", ".join(f"`{t}/`" for t in r.tree_scope)
        else:
            tier = "per-file"
            scope = ", ".join(f"`{t}/`" for t in r.tree_scope)
        lines.append(f"| `{r.id}` | {r.name} | {tier} | {scope} | "
                     f"{r.severity} | {r.summary} |")
    lines.append("")
    for r in rules:
        lines.append(f"### {r.id} — {r.name}")
        lines.append("")
        lines.append(f"**Invariant:** {r.summary}.")
        lines.append("")
        lines.append(r.rationale)
        lines.append("")
    return "\n".join(lines).rstrip() + "\n"


def main() -> int:
    text = render()
    if "--check" in sys.argv:
        try:
            with open(OUT_PATH, encoding="utf-8") as f:
                current = f.read()
        except OSError:
            current = ""
        if current != text:
            print(f"{OUT_PATH} is stale; run python hack/gen_lint_docs.py",
                  file=sys.stderr)
            return 1
        print(f"{OUT_PATH} is up to date")
        return 0
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w", encoding="utf-8") as f:
        f.write(text)
    print(f"wrote {OUT_PATH} ({len(text.splitlines())} lines)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
