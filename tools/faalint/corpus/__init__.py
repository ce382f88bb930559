"""The faalint regression corpus: pre-fix snippets of the bugs this
repo shipped and then fixed by hand, each pinned to the pass that must
now catch it statically, plus the post-fix shape that must stay clean
(zero false positives).

``check_corpus()`` is the machine gate behind ``python -m tools.faalint
--selfcheck`` and the test suite: every prefix snippet is flagged by
EXACTLY the expected rules (and so by exactly one pass), every postfix
snippet produces zero findings.
"""

from __future__ import annotations

import os

from ..engine import check_source, default_rules

_HERE = os.path.dirname(os.path.abspath(__file__))

#: name -> (lint-as relpath, expected rule ids, intended pass).  The
#: relpath places the snippet in the scope the real bug lived in.
CASES = {
    # the three historical incidents named in docs/STATIC_ANALYSIS.md
    "watchdog_ema_race": (
        "fast_autoaugment_tpu/core/watchdog.py", {"C2"}, "concurrency"),
    "lease_remove_recreate": (
        "fast_autoaugment_tpu/launch/workqueue.py", {"C3"}, "concurrency"),
    "blocking_admission": (
        "fast_autoaugment_tpu/serve/policy_server.py", {"R6"},
        "robustness"),
    # the measured dispatch pathologies (PR 4 / docs/PARITY.md)
    "mixed_commit": (
        "fast_autoaugment_tpu/train/trainer.py", {"D3"}, "dispatch"),
    "host_sync_loop": (
        "fast_autoaugment_tpu/train/trainer.py", {"D1"}, "dispatch"),
    "jit_in_loop": (
        "fast_autoaugment_tpu/train/trainer.py", {"D2"}, "dispatch"),
    # the per-request copy tax the zero-copy data plane removed
    "npz_per_request": (
        "fast_autoaugment_tpu/serve/serve_cli.py", {"D4"}, "dispatch"),
    # the byte-identical-artifact contract
    "wallclock_pid_payload": (
        "fast_autoaugment_tpu/core/checkpoint.py", {"T1", "T3"},
        "determinism"),
    "unsorted_listdir": (
        "fast_autoaugment_tpu/core/checkpoint.py", {"T2"}, "determinism"),
}

#: the three pre-fix snippets of shipped-then-fixed incidents the
#: acceptance criteria name explicitly
HISTORICAL = ("watchdog_ema_race", "lease_remove_recreate",
              "blocking_admission")


def load(name: str, which: str = "prefix") -> str:
    with open(os.path.join(_HERE, f"{which}_{name}.py")) as fh:
        return fh.read()


def rule_pass_map() -> dict[str, str]:
    return {r.id: r.pass_name for r in default_rules()}


def check_case(name: str) -> list[str]:
    """Problems (empty = ok) for one corpus case: prefix flagged by
    exactly the expected rules of exactly the intended pass, postfix
    clean."""
    relpath, expected, intended_pass = CASES[name]
    passes = rule_pass_map()
    problems = []
    got = check_source(load(name, "prefix"), relpath)
    rules = {f.rule for f in got}
    if rules != expected:
        problems.append(
            f"{name}: prefix expected rules {sorted(expected)}, "
            f"got {sorted(rules)} ({[repr(f) for f in got]})")
    wrong_pass = {f.rule for f in got if passes.get(f.rule) != intended_pass}
    if wrong_pass:
        problems.append(
            f"{name}: prefix flagged by passes other than "
            f"{intended_pass}: {sorted(wrong_pass)}")
    post = check_source(load(name, "postfix"), relpath)
    if post:
        problems.append(
            f"{name}: postfix (fixed shape) is NOT clean: "
            f"{[repr(f) for f in post]}")
    return problems


def check_corpus() -> list[str]:
    problems: list[str] = []
    for name in sorted(CASES):
        problems.extend(check_case(name))
    return problems
