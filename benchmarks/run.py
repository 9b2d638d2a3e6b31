# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV
# and writes machine-readable BENCH_<module>.json perf-trajectory artifacts
# (throughput, recall, modeled I/O per config) so future changes can diff
# performance against the committed numbers.
#
# ``--smoke`` runs every driver at tiny sizes (<60 s total) and asserts the
# output schema, so CI exercises the benchmark code paths instead of leaving
# them hand-run only (a ``slow``-marked pytest invokes this mode).
import argparse
import contextlib
import io
import json
import os
import re
import sys
import time
import traceback

ROW_RE = re.compile(r"^[^,\s][^,]*,\d+(\.\d+)?,[^,]*(;[^,]*)*$")

# modules whose rows form the tracked perf trajectory
ARTIFACT_MODS = ("query", "streaming", "serving")


def _engine_summary() -> dict:
    """Cumulative verification-engine counters (compile churn + transfer
    volume) for the artifact, so perf diffs can tell compute regressions
    from compile/transfer regressions."""
    from repro.core.verify_engine import get_engine

    out = dict(get_engine().stats)
    # copy the served-batch histogram so the artifact snapshot does not
    # alias the engine's live (still-mutating) counter dict
    out["batch_hist"] = {str(kk): v for kk, v in out["batch_hist"].items()}
    return out


def _write_artifact(name: str, rows: list, extras: dict, out_dir: str,
                    smoke: bool) -> None:
    # smoke artifacts get their own (gitignored) name so CI runs never
    # overwrite the committed perf trajectory
    suffix = ".smoke.json" if smoke else ".json"
    path = os.path.join(out_dir, f"BENCH_{name}{suffix}")
    payload = {
        "benchmark": name,
        "smoke": smoke,  # smoke numbers are schema checks, not perf points
        "unix_time": int(time.time()),
        "verify_engine": _engine_summary(),
        "rows": rows,
    }
    for key, val in extras.items():
        if key in payload:
            raise AssertionError(f"EXTRAS key {key!r} collides with the "
                                 "artifact's own payload fields")
        payload[key] = val
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + output-schema assertions")
    ap.add_argument("--only", default=None,
                    help="comma-separated module names (e.g. query,streaming)")
    ap.add_argument(
        "--out-dir",
        default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        help="where BENCH_<module>.json artifacts are written (repo root)")
    args = ap.parse_args(argv)

    from . import common, construction, memory, query, serving, streaming

    mods = [construction, query, streaming, serving, memory]
    if args.only:
        wanted = set(args.only.split(","))
        mods = [m for m in mods if m.__name__.split(".")[-1] in wanted]

    failures = 0
    print("name,us_per_call,derived")
    for mod in mods:
        name = mod.__name__.split(".")[-1]
        common.ROWS.clear()
        common.EXTRAS.clear()
        try:
            if args.smoke:
                buf = io.StringIO()
                with contextlib.redirect_stdout(buf):
                    mod.main(smoke=True)
                out = buf.getvalue()
                for line in filter(None, out.splitlines()):
                    if not ROW_RE.match(line):
                        raise AssertionError(
                            f"{name}: row violates name,us,derived schema: {line!r}"
                        )
                sys.stdout.write(out)
            else:
                mod.main()
            if name in ARTIFACT_MODS:
                _write_artifact(name, list(common.ROWS), dict(common.EXTRAS),
                                args.out_dir, args.smoke)
        except Exception:  # noqa: BLE001 — keep the harness running
            failures += 1
            print(f"{name}/ERROR,0.0,")
            traceback.print_exc()
    return 1 if (args.smoke and failures) else 0


if __name__ == "__main__":
    from repro.launch.jax_cache import use_persistent_cache

    use_persistent_cache()
    sys.exit(main())
