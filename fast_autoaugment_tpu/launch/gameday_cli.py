"""``python -m fast_autoaugment_tpu.launch.gameday_cli`` — trace-driven
game days (docs/GAMEDAYS.md).

Thin front end over ``gameday/runner.py``: pick scenarios, run them,
print the verdict table, exit 0 only when the SUITE is green (every
verdict matched its spec's ``expect`` — a broken-config scenario that
failed on cue is green; one that passed is not).

The suite JSON (``--out``) carries two provenance stamps,
``single_core_caveat`` and ``platform: cpu``: every plane process is
spawned on the CPU, so a verdict says the plane behaved, never how
fast a chip is.  All filesystem work lives in the runner — this module
stays FS-free (faalint F1 polices ``launch/``).

Examples::

    python -m fast_autoaugment_tpu.launch.gameday_cli --list
    python -m fast_autoaugment_tpu.launch.gameday_cli --suite \\
        --out docs/gameday.json                       # make gameday
    python -m fast_autoaugment_tpu.launch.gameday_cli --suite --smoke
    python -m fast_autoaugment_tpu.launch.gameday_cli \\
        --scenario flash-crowd-10x --seed 21
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="gameday",
        description="deterministic game-day drills with journaled "
                    "verdicts over the live serving plane")
    p.add_argument("--scenario", action="append", default=None,
                   metavar="NAME",
                   help="run one named scenario (repeatable); default "
                        "is the full suite")
    p.add_argument("--suite", action="store_true",
                   help="run the full registered suite, broken-config "
                        "demonstrations included (the default when no "
                        "--scenario is given)")
    p.add_argument("--smoke", action="store_true",
                   help="time/load-shrunk pass over the same topologies "
                        "and predicates (scenario.scaled)")
    p.add_argument("--smoke-factor", type=float, default=0.4,
                   help="load shrink factor for --smoke (default 0.4; "
                        "dispatch floors scale inversely so overload "
                        "scenarios still overload)")
    p.add_argument("--seed", type=int, default=None,
                   help="override every scenario's seed (same "
                        "(scenario, seed) => byte-identical schedule)")
    p.add_argument("--out", default=None, metavar="PATH",
                   help="write the suite JSON (records + verdict table "
                        "+ provenance stamps) here")
    p.add_argument("--keep", action="store_true",
                   help="keep the per-scenario workdirs (journals, "
                        "policies) for post-mortem instead of deleting")
    p.add_argument("--list", action="store_true",
                   help="list registered scenarios and exit")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from fast_autoaugment_tpu.gameday.scenario import SCENARIOS, suite_names

    if args.list:
        for name in suite_names():
            s = SCENARIOS[name]
            print(f"{name} (expect {s.expect}): {s.summary}")
        return 0

    names = suite_names() if (args.suite or not args.scenario) \
        else list(args.scenario)
    unknown = [n for n in names if n not in SCENARIOS]
    if unknown:
        print(f"unknown scenario(s): {', '.join(unknown)}; "
              f"--list shows the registry", file=sys.stderr)
        return 2

    # provenance stamps ride the suite JSON.  "platform": every plane
    # process is spawned with JAX_PLATFORMS=cpu (gameday/runner.py) —
    # a game day never touches the chip
    extra = {"single_core_caveat": True, "platform": "cpu"}

    from fast_autoaugment_tpu.gameday.runner import run_suite
    result = run_suite(names, smoke=args.smoke,
                       smoke_factor=args.smoke_factor, seed=args.seed,
                       out=args.out, keep=args.keep, extra=extra)
    print(result["table"])
    return 0 if result["suite_green"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
