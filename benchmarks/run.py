# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV. ``--bench`` instead runs the registered BENCH_*.json suites
# (fleet/network/qos) so one entrypoint refreshes every trajectory file.
import argparse
import os
import sys
import traceback

# Script-mode friendliness (`python benchmarks/run.py`): the repo root
# must be importable for the `benchmarks.*` suite modules.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bench_fleet(check):
    from benchmarks.fleet_scaling import main
    return main(["--check-determinism"] if check else [])


def _bench_network(check):
    from benchmarks.network_contention import main
    return main(["--check-determinism"] if check else [])


def _bench_qos(check):
    from benchmarks.qos_compute import main
    return main(["--check-determinism"] if check else [])


def _bench_replay(check):
    from benchmarks.replay_policy_search import main
    return main(["--check-determinism"] if check else [])


def _bench_cache(check):
    from benchmarks.weight_cache import main
    return main(["--check-determinism"] if check else [])


def _bench_sim(check):
    # sim_profile has no determinism flag (it is a pure timing/memory
    # profile; the obs determinism lives in its --smoke gate and tests)
    from benchmarks.sim_profile import main
    return main([])


# BENCH_*.json writers: each returns a process-style exit code (0 = all
# assertions held) and writes its own JSON next to the repo root.
ALL_BENCH = {
    "fleet": _bench_fleet,       # BENCH_fleet.json
    "network": _bench_network,   # BENCH_network.json
    "qos": _bench_qos,           # BENCH_qos.json
    "replay": _bench_replay,     # BENCH_replay.json
    "sim": _bench_sim,           # BENCH_sim.json
    "cache": _bench_cache,       # BENCH_cache.json
}


def run_benches(names, check: bool = True) -> int:
    failures = 0
    for name in names:
        print(f"== bench: {name} ==")
        try:
            rc = ALL_BENCH[name](check)
        except Exception as e:
            rc = 1
            print(f"{name},ERROR,{type(e).__name__}: {e}", file=sys.stderr)
            traceback.print_exc(file=sys.stderr)
        if rc:
            failures += 1
        sys.stdout.flush()
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument("--bench", default=None,
                    metavar="all|fleet,network,qos,replay,sim,cache",
                    help="refresh the BENCH_*.json suites instead of the "
                         "paper-figure CSV benches")
    ap.add_argument("--no-determinism", action="store_true",
                    help="skip the replay determinism checks in --bench runs")
    args = ap.parse_args()

    if args.bench:
        names = (list(ALL_BENCH) if args.bench == "all"
                 else args.bench.split(","))
        unknown = [n for n in names if n not in ALL_BENCH]
        if unknown:
            raise SystemExit(f"unknown bench(es): {unknown}; "
                             f"known: {sorted(ALL_BENCH)}")
        if run_benches(names, check=not args.no_determinism):
            raise SystemExit(1)
        return

    from benchmarks.paper_figs import ALL_FIGS
    from benchmarks.roofline import ALL_ROOFLINE

    suites = {**ALL_FIGS, **ALL_ROOFLINE}
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites.items():
        if only and name not in only:
            continue
        try:
            for row_name, us, derived in fn():
                print(f"{row_name},{us:.1f},{derived}")
        except Exception as e:
            failures += 1
            print(f"{name},ERROR,{type(e).__name__}: {e}", file=sys.stderr)
            traceback.print_exc(file=sys.stderr)
        sys.stdout.flush()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
