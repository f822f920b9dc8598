"""Benchmark harness entry point — one benchmark per paper table/figure:

    Table 1  program characteristics       table1_characteristics
    Fig. 5   PopPy vs Python speedups      fig5_speedup (async + sync clients)
    Fig. 10  blocking-external offload     fig10_sync_offload
    Fig. 11  effect-domain keying          fig11_effect_domains
    Fig. 12  auto-batching                 fig12_autobatch
    Fig. 13  prefix-aware prefill          fig13_prefix_prefill
    Fig. 16  speculative execution         fig16_speculation
    Fig. 17  durability / chaos            fig17_durability
    Fig. 6   ToT execution trace           fig6_trace
    Fig. 7   interpreter overhead          fig7_overhead
    Fig. 8   parallelism scaling           fig8_scaling
    §Roofline  per-(arch×shape) terms      roofline (subprocess, 512 devs)

    PYTHONPATH=src python -m benchmarks.run [--quick] [--skip-roofline]
    PYTHONPATH=src python -m benchmarks.run --smoke     # CI equivalence job

Results land in experiments/apps/ and experiments/roofline/; ``--smoke``
additionally writes the machine-readable ``BENCH_smoke.json`` consumed by
the ``bench-gate`` CI job (benchmarks/perf_gate.py).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

#: Where --smoke writes its machine-readable result summary.
SMOKE_JSON = "experiments/ci/BENCH_smoke.json"


def smoke(out_path=SMOKE_JSON):
    """Benchmark smoke job (CI): run fig5/fig9/fig10/fig11/fig12/fig13
    with tiny parameters.  Every one of these figures asserts result
    equality (and, for fig5/fig11/fig12/fig13, ≡_A trace equivalence)
    against sequential-mode Python on every trial — so an equivalence
    regression fails this job in minutes instead of surfacing in a full
    benchmark run.  Speedup
    acceptance bars are *not* enforced here (tiny N is timing noise);
    correctness is — but every figure's speedups are recorded in
    ``BENCH_smoke.json`` (per-figure ``equivalent`` boolean + ``speedups``
    map) so the ``bench-gate`` CI job can track the trajectory against
    ``benchmarks/baseline.json``."""
    from benchmarks import (fig5_speedup, fig9_dispatch, fig10_sync_offload,
                            fig11_effect_domains, fig12_autobatch,
                            fig13_prefix_prefill, fig14_paged_kv,
                            fig15_fleet, fig16_speculation, fig17_durability,
                            obs_overhead)

    t0 = time.time()
    figures = {}

    def attempt(name, title, fn, extract):
        print(f"== smoke: {name} ({title}) ==", flush=True)
        try:
            r = fn()
            figures[name] = {"equivalent": True, "speedups": extract(r)}
        except AssertionError as e:
            figures[name] = {"equivalent": False, "error": str(e),
                             "speedups": {}}
            print(f"EQUIVALENCE FAILURE [{name}]: {e}", flush=True)
        print(flush=True)

    # the smoke fig5 run is span-traced end to end; the resulting
    # Perfetto trace is uploaded as a CI artifact (debugging a CI-only
    # perf regression starts from this file)
    attempt("fig5", "equality + ≡_A per trial",
            lambda: fig5_speedup.run(trials=1, scale=0.1, camel_count=2,
                                     trace_out="experiments/ci/"
                                               "smoke_trace.json"),
            lambda r: {"geomean": r[1]["geomean"]})
    attempt("fig9", "dispatch preserves sequential semantics",
            lambda: fig9_dispatch.run(trials=1, scale=0.3),
            lambda r: {"routed": r["speedup_routed"],
                       "warm": r["speedup_warm"]})
    attempt("fig10", "offload result equality",
            lambda: fig10_sync_offload.run(trials=1, delay=0.05,
                                           sweep=(2, 4), smoke=True),
            lambda rows: {"offload_n4": next(
                x["speedup"] for x in rows if x["n"] == 4)})
    attempt("fig11", "per-domain equality + ≡_A per trial",
            lambda: fig11_effect_domains.run(trials=1, scale=0.1,
                                             sweep=(2, 4), n_steps=3,
                                             smoke=True),
            lambda rows: {"keyed_vs_single_k4": next(
                x["speedup_vs_single"] for x in rows
                if x["k_agents"] == 4)})
    attempt("fig12", "batched equality + ≡_A per trial",
            lambda: fig12_autobatch.run(trials=1, n_docs=8, scale=0.3,
                                        smoke=True),
            lambda r: {"batched_vs_unbatched":
                       r["speedup_batched_vs_unbatched"],
                       "batched_vs_plain": r["speedup_batched_vs_plain"]})
    # fig13 additionally asserts the prefill jit-compilation bound every
    # run; jit_headroom (= bound / compilations) is tracked by the gate so
    # a bucketing regression (recompile-per-length) fails CI even when
    # the hard bound still holds at smoke scale
    attempt("fig13", "token equality + ≡_A + prefill-compilation bound",
            lambda: fig13_prefix_prefill.run(trials=1, n=8,
                                             prefix_chars=400, smoke=True),
            lambda r: {"prefix_vs_nocache":
                       r["speedup_prefix_vs_nocache"],
                       "jit_headroom": r["jit_headroom"]})
    # fig14 asserts token-exactness + ≡_A + zero-copy admission + both
    # compile bounds every trial; admitted_users_ratio is a capacity
    # count (not a timing), so the gate tracks it even at smoke scale,
    # and jit_headroom guards against recompile-per-length on the paged
    # prefill path
    attempt("fig14", "paged-KV token equality + ≡_A + zero-copy + "
                     "compile bounds",
            lambda: fig14_paged_kv.run(trials=1, smoke=True),
            lambda r: {"admitted_users_ratio": r["admitted_users_ratio"],
                       "jit_headroom": r["jit_headroom"]})
    # fig15 asserts token-exactness + ≡_A of every fleet run vs the
    # single-replica fleet and the sequential oracle, the strict
    # affinity > least-outstanding warm-route rate gap (read from the
    # per-replica dispatch counters), per-replica compile bounds, and the
    # ≥2.5× 4-vs-1-replica drain bar — the scaling ratio counts overlapped
    # simulated device steps, so it holds at smoke scale; the TP leg runs
    # whenever ≥2 devices are visible (the multi-device CI job sets
    # XLA_FLAGS=--xla_force_host_platform_device_count=8)
    attempt("fig15", "fleet token equality + ≡_A + affinity > "
                     "least-outstanding + ≥2.5× scale-out",
            lambda: fig15_fleet.run(trials=1, smoke=True),
            lambda r: {"fleet_scaling_x4": r["fleet_scaling_x4"],
                       "affinity_hit_rate": r["affinity_hit_rate"]})
    # fig16 asserts, on every trial, result equality + ≡_A of both the
    # non-speculative and speculative runs against the sequential oracle,
    # zero committed effects from losing arms, the bounded wasted-work
    # ratio, perfect predictor validation, and race-loser drain through
    # the dispatcher — so a speculation-soundness regression (a loser
    # effect committing, a leaked admission, an unvalidated guess
    # escaping) fails this job even at smoke scale; the ≥2× speedup bar
    # is enforced only in full runs, but spec_vs_nonspec is tracked by
    # the gate
    attempt("fig16", "speculative equality + ≡_A + zero loser effects + "
                     "bounded waste + race drain",
            lambda: fig16_speculation.run(trials=1, call_s=0.01,
                                          smoke=True),
            lambda r: {"spec_vs_nonspec":
                       r["branchy"]["speedup_spec_vs_nonspec"],
                       "race": r["race"]["speedup_race"]})
    # fig17 is the chaos leg: a subprocess is hard-killed (os._exit) mid-
    # journal and resumed — asserting byte-identical results + ≡_A vs the
    # uninterrupted run and a ≥80% journal-replay fraction (the gated
    # recovery_replay_fraction metric, baseline 1.0 with the gate's 0.2
    # tolerance = the ISSUE's 0.8 floor); plus seeded dispatcher fault
    # injection with zero leaked admissions and the breaker's full
    # open → probe → close cycle, and injected serving-backend failures
    # leaving decode slots / KV pages / prefix pins exactly balanced
    attempt("fig17", "kill/resume byte-identical + ≡_A + ≥80% replay + "
                     "zero leaks under injected faults",
            lambda: fig17_durability.run(trials=1, smoke=True),
            lambda r: {"recovery_replay_fraction":
                       r["recovery"]["recovery_replay_fraction"]})
    # obs_overhead asserts the tracing-enabled overhead bar (<5% pairwise
    # delta on fig5 tiny-N) and critical-path attribution soundness; an
    # assertion failure surfaces through the same equivalence machinery
    attempt("obs_overhead", "tracing <5% overhead + attribution ≥85%",
            lambda: obs_overhead.run(),
            lambda r: {"disabled_vs_enabled": r["disabled_vs_enabled"]})

    out = Path(out_path)
    out.parent.mkdir(parents=True, exist_ok=True)
    payload = {"figures": figures,
               "elapsed_s": round(time.time() - t0, 1)}
    out.write_text(json.dumps(payload, indent=1))
    print(f"wrote {out}")
    failed = [n for n, f in figures.items() if not f["equivalent"]]
    if failed:
        print(f"benchmark smoke FAILED (equivalence): {', '.join(failed)}")
        return 1
    print(f"benchmark smoke passed in {time.time() - t0:.0f}s")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer trials / smaller sweeps")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-N equivalence smoke (fig5/9/10/11); "
                         "used by CI")
    ap.add_argument("--skip-roofline", action="store_true",
                    help="skip the 512-device roofline subprocess")
    ap.add_argument("--roofline-arch", action="append", default=None)
    args = ap.parse_args()

    if args.smoke:
        return smoke()

    trials = 2 if args.quick else 3
    t0 = time.time()

    if not args.skip_roofline:
        # a compile-only dry-run on virtual CPU devices: pinned to the CPU
        # so it never asks for an accelerator, and run before this process
        # touches JAX
        print("=" * 72)
        print("§Roofline — per-(arch × shape) terms from the compiled "
              "dry-run (512-device subprocess)")
        print("=" * 72)
        sys.stdout.flush()  # keep tee ordering across the subprocess
        cmd = [sys.executable, "-m", "benchmarks.roofline"]
        for a in (args.roofline_arch or []):
            cmd += ["--arch", a]
        if args.quick:
            for a in ("qwen3-14b", "olmoe-1b-7b", "mamba2-2.7b"):
                cmd += ["--arch", a]
        r = subprocess.run(cmd, env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if r.returncode != 0:
            print("roofline subprocess failed", file=sys.stderr)
            return 1
        print()

    from benchmarks import (fig5_speedup, fig6_trace, fig7_overhead,
                            fig8_scaling, fig10_sync_offload,
                            fig11_effect_domains, fig12_autobatch,
                            fig13_prefix_prefill, fig14_paged_kv,
                            fig15_fleet, fig16_speculation,
                            fig17_durability, table1_characteristics)

    print("=" * 72)
    print("Table 1 — benchmark program characteristics")
    print("=" * 72)
    table1_characteristics.run(trials=trials)

    print("\n" + "=" * 72)
    print("Fig. 5 — median speedup of PopPy over standard Python")
    print("=" * 72)
    fig5_speedup.run(trials=trials,
                     camel_count=6 if args.quick else 30)

    print("\n" + "=" * 72)
    print("Fig. 5 (sync clients) — same apps, blocking SDK externals")
    print("=" * 72)
    fig5_speedup.run(trials=trials, camel_count=6 if args.quick else 30,
                     sync_externals=True)

    print("\n" + "=" * 72)
    print("Fig. 10 — executor offload: overlap of blocking externals")
    print("=" * 72)
    fig10_sync_offload.run(trials=trials)

    print("\n" + "=" * 72)
    print("Fig. 11 — effect-domain keying: independent sequential chains")
    print("=" * 72)
    if args.quick:
        fig11_effect_domains.run(trials=trials, sweep=(2, 4))
    else:
        fig11_effect_domains.run(trials=trials)

    print("\n" + "=" * 72)
    print("Fig. 12 — auto-batching of pending unordered externals")
    print("=" * 72)
    fig12_autobatch.run(trials=trials,
                        n_docs=8 if args.quick else 32)

    print("\n" + "=" * 72)
    print("Fig. 13 — prefix-aware KV reuse + bucketed chunked prefill")
    print("=" * 72)
    fig13_prefix_prefill.run(trials=trials,
                             n=8 if args.quick else 16)

    print("\n" + "=" * 72)
    print("Fig. 14 — paged KV: admitted users at fixed memory, zero-copy "
          "prefix sharing")
    print("=" * 72)
    fig14_paged_kv.run(trials=trials)

    print("\n" + "=" * 72)
    print("Fig. 15 — replica fleet: routed scale-out + prefix-affinity "
          "placement")
    print("=" * 72)
    fig15_fleet.run(trials=trials)

    print("\n" + "=" * 72)
    print("Fig. 16 — speculation: branchy routing cascade, predicted "
          "routes, racing rollouts")
    print("=" * 72)
    fig16_speculation.run(trials=trials)

    print("\n" + "=" * 72)
    print("Fig. 17 — durability: kill/resume recovery, fault injection, "
          "breaker")
    print("=" * 72)
    fig17_durability.run(trials=trials)

    print("\n" + "=" * 72)
    print("Fig. 6 — ToT execution trace (queue → dispatch → resolve)")
    print("=" * 72)
    fig6_trace.run()

    print("\n" + "=" * 72)
    print("Fig. 7 — interpreter overhead (all externals forced sequential)")
    print("=" * 72)
    fig7_overhead.run(trials=trials)

    print("\n" + "=" * 72)
    print("Fig. 8 — speedup vs available parallelism")
    print("=" * 72)
    if args.quick:
        fig8_scaling.run(trials=1, beams=(1, 5, 10), assessments=(1, 5, 10))
    else:
        fig8_scaling.run(trials=trials)

    print(f"\nall benchmarks done in {time.time()-t0:.0f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
