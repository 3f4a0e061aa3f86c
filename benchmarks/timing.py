"""Shared measurement helpers for bench.py and benchmarks/scenarios.py.

Two rules this method rests on; whether either is still needed on the
local chip is not measured (ROADMAP S1 replaces the method with a
profiler trace, D1 retires what has no cause):

* Every sync is a value fetch (``float``/``np.asarray``): the fetched
  value cannot exist before the work that produces it has run.
* A single dispatch pays a fixed host cost that can bury a sub-ms
  program; ``measure_program_slopes`` runs K steps inside ONE jitted
  ``lax.fori_loop`` at two trip counts and reports the slope
  (t_hi − t_lo)/(K_hi − K_lo), which cancels the fixed cost exactly. The
  loop body feeds a runtime-zero function of the output back into the
  input (watts ≥ 0 ⇒ min(Σwatts, 0) == 0, but XLA can't prove it), so
  every iteration depends on the previous one and nothing hoists.
"""

from __future__ import annotations

# keplint: monotonic-only — bench timings use perf_counter only

import math
import time


def percentiles(fn, warm: int, iters: int) -> tuple[float, float]:
    """(p99_ms, p50_ms) of ``fn()`` wall time; caller syncs inside fn."""
    for _ in range(warm):
        fn()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    return (times[math.ceil(0.99 * len(times)) - 1],  # nearest-rank p99
            times[len(times) // 2])


def measure_program_slopes(program, params, args, k_lo: int, k_hi: int,
                           repeats: int) -> list[float]:
    """→ sorted ms-per-iteration slope samples for ``program(params, *args)``.

    ``args`` is a tuple of device arrays, consumed (donated); the feedback
    rides on EVERY inexact-dtype input (an input left untouched would be
    loop-invariant, letting XLA hoist whatever consumes only it out of the
    loop — e.g. an estimator that reads just the feature windows), and the
    program's output pytree is summed (all leaves are non-negative
    energies/powers in this codebase, so min(sum, 0) is a runtime zero).
    The spread (k_hi − k_lo) × program_time must clear the platform's
    per-dispatch jitter.
    """
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(1,))
    def loop(model_params, args, k):
        def body(_, carry):
            args, acc = carry
            out = program(model_params, *args)
            s = sum(jnp.sum(leaf.astype(jnp.float32))
                    for leaf in jax.tree.leaves(out))
            zero = jnp.minimum(s, 0.0)
            args = tuple(
                a + zero.astype(a.dtype)
                if jnp.issubdtype(a.dtype, jnp.inexact) else a
                for a in args)
            return args, acc + s

        return jax.lax.fori_loop(0, k, body, (tuple(args), jnp.float32(0)))

    def timed(args, k):
        t0 = time.perf_counter()
        args, acc = loop(model_params=params, args=args, k=jnp.int32(k))
        float(acc)  # scalar D2H: the value fetch is the sync
        return args, (time.perf_counter() - t0) * 1e3

    # compile+warm both trip counts (k is traced → one compile)
    args, _ = timed(tuple(args), k_lo)
    args, _ = timed(args, k_hi)
    slopes = []
    for _ in range(repeats):
        args, t_lo = timed(args, k_lo)
        args, t_hi = timed(args, k_hi)
        slopes.append(max(0.0, (t_hi - t_lo) / (k_hi - k_lo)))
    slopes.sort()
    return slopes
