"""Run the whole pre-commit quick tier with ONE command and ONE exit code.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Each check is a standalone script that asserts bit-identity (or audits
the HLO) and exits nonzero on failure; this runner executes them as
subprocesses (each needs its own fresh jax process — several reconfigure
the virtual device count at import) and aggregates:

    JAX_PLATFORMS=cpu python tools/quick_all.py            # all checks
    JAX_PLATFORMS=cpu python tools/quick_all.py route agg  # a subset

Exit code 0 iff every selected check passed. A check crossing its
per-check timeout counts as FAILED.
"""

import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# name -> (script, per-check timeout seconds, extra argv, extra env)
CHECKS = {
    "lint": ("graftlint.py", 120, (), {}),
    "route": ("quick_route_check.py", 300, (), {}),
    "fanout": ("quick_fanout_check.py", 300, (), {}),
    "pipeline": ("pipeline_check.py", 300, (), {}),
    "join": ("quick_join_check.py", 300, (), {}),
    "agg": ("quick_agg_check.py", 300, (), {}),
    # ingest front door: event vs wire-format vs parallel-pack(pool=2)
    # paths bit-identical and identically ordered through enforceOrder
    "ingest": ("quick_ingest_check.py", 300, (), {}),
    # cluster fabric (siddhi_tpu/cluster/): 2 real worker processes,
    # split + pinned apps, a mid-feed checkpoint barrier — merged egress
    # must exactly equal the single-process run (ISSUE 17)
    "cluster": ("quick_cluster_check.py", 300, (), {}),
    "hlo": ("hlo_audit.py", 300, (), {}),
    # process-global compiled-program cache (core/util/program_cache.py):
    # two identical apps -> one compile + bit-identical outputs, warm
    # blue/green attach with identity-pinned eviction, knob-off control
    "programs": ("quick_programs_check.py", 300, (), {}),
    # critical-path profiler: bit-identity with FULL profiling on
    # (journeys + cost capture + tracer + detail stats) + report sanity
    "obs": ("quick_obs_check.py", 300, (), {}),
    # semantic fuzzing (siddhi_tpu/fuzz/): a fast seeded corpus subset
    # through the full live strategy matrix — generated apps, exact
    # output diffs vs the all-legacy baseline, eligibility-census audit.
    # The soak-class run is tools/fuzz_equivalence.py --seed 0 --cases 200
    "fuzz": ("fuzz_equivalence.py", 300,
             ("--seed", "0", "--quick"), {}),
    # autopilot axis (siddhi_tpu/autopilot/): the same seeded quick
    # subset with the closed-loop controller ON at an aggressive
    # cadence — live knob actuations mid-feed must stay bit-identical
    # to the all-legacy baseline
    "autopilot": ("fuzz_equivalence.py", 300,
                  ("--seed", "0", "--quick", "--autopilot"), {}),
    # the sanitized pass: the fast bit-identity subset re-run with every
    # runtime sanitizer armed (transfer guard, recompile watchdog,
    # lock-order assertions — siddhi_tpu/analysis/sanitize.py). For the
    # FULL tier under sanitizers run:
    #   SIDDHI_TPU_SANITIZE=1 python tools/quick_all.py route fanout \
    #       pipeline join agg hlo
    # budget = the four sub-checks' own budgets plus headroom for the
    # nested runner's per-check interpreter/jax startup: sanitize mode
    # is strictly slower per call, so the nested run must not get LESS
    # time than its parts would alone
    "sanitize": ("quick_all.py", 1350,
                 ("route", "fanout", "pipeline", "agg"),
                 {"SIDDHI_TPU_SANITIZE": "1"}),
}


def main() -> int:
    explicit = sys.argv[1:]
    names = explicit or list(CHECKS)
    unknown = [n for n in names if n not in CHECKS]
    if unknown:
        print(f"unknown check(s) {unknown}; available: {list(CHECKS)}")
        return 2
    base_env = dict(os.environ)
    base_env.setdefault("JAX_PLATFORMS", "cpu")
    if not explicit and base_env.get(
            "SIDDHI_TPU_SANITIZE", "").strip().lower() in (
            "1", "true", "on", "yes"):     # same spellings sanitize.enabled()
        # a DEFAULT run inside an already-sanitized environment skips
        # the nested "sanitize" entry — everything is sanitized anyway.
        # An EXPLICIT `quick_all.py sanitize` still runs it (its
        # subprocess names the subset, so there is no recursion), and
        # an explicit =0 is NOT sanitized: the pass still runs.
        names = [n for n in names if n != "sanitize"]
    if not names:
        print("quick_all: nothing to run")
        return 2
    results = {}
    t00 = time.time()
    for name in names:
        script, timeout, extra_argv, extra_env = CHECKS[name]
        t0 = time.time()
        env = {**base_env, **extra_env}
        print(f"[quick_all] {name}: {script} ...", flush=True)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(HERE, script), *extra_argv],
                env=env, timeout=timeout, capture_output=True, text=True)
            ok = proc.returncode == 0
            tail = (proc.stdout + proc.stderr).strip().splitlines()[-8:]
        except subprocess.TimeoutExpired:
            ok, tail = False, [f"TIMEOUT after {timeout}s"]
        results[name] = ok
        status = "PASS" if ok else "FAIL"
        print(f"[quick_all] {name}: {status} in {time.time() - t0:.1f}s",
              flush=True)
        if not ok:
            for line in tail:
                print(f"    {line}", flush=True)
    failed = [n for n, ok in results.items() if not ok]
    print(f"[quick_all] {len(results) - len(failed)}/{len(results)} checks "
          f"passed in {time.time() - t00:.1f}s"
          + (f" — FAILED: {failed}" if failed else ""), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
