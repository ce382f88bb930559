"""``programs/train_tokens.py`` for a model whose training loss has a second
term from a multi-token-prediction (MTP) module: the same run, window,
counters and checks, by import and unchanged, with the reference comparison
made **for both heads**.

``train_tokens.py`` compares one array of logits from ``model.apply(...,
train=False)``; a module that is computed in training alone is outside
that comparison.  Here the system's model is asked for both heads' logits
(``logits_and_mtp_logits``: inputs and the token after each) from the
window's last checkpoint, as deployed and under ``highest``, each pass
with the experts it chose — the module's block among the layers — and the
configuration's plain reference is given that choice.  The checks, beside
``train_tokens.py``'s own names:

``reference_logits``, ``reference_logits_float32``, ``routing``, ``routing_float32``
    the main head and the main layers' choice of experts, as in
    ``train_tokens.py``;
``reference_mtp_logits``, ``reference_mtp_logits_float32``
    the module's head, by the configuration's two ``mtp_logit_tolerance`` s;
``routing_mtp``, ``routing_mtp_float32``
    the module's block's choice against the reference's own scores, by the
    two ``routing_margin_tolerance`` s.

One program of the system (both heads, both precisions) and one of the
reference (both heads) are compiled from shapes beside the checkpoint's
writing, as ``train_tokens.py`` compiles its two.

``train_tokens.run`` finds its comparison by two module-level names,
``ComparisonsAhead`` and ``reference_checks``; :func:`run` puts this file's
in their place for the length of the call.  A model without the method
(a program from before the module) fails the cell cleanly: the thread
hands the error to :func:`reference_checks`.
"""

from __future__ import annotations

import contextlib
import threading

import numpy as np

from benchmarks.harness import window as win
from benchmarks.harness.observed import Observed
from benchmarks.harness.spec import Cell
from benchmarks.programs import train_tokens

MTP_LAYER = "mtp"


class BothHeadsAhead(train_tokens.ComparisonsAhead):
    """``ComparisonsAhead`` whose two programs yield both heads' logits."""

    def run(self) -> None:
        import jax

        def system(p, x, ahead):
            """((logits, mtp_logits, routing) as deployed, the same under
            highest)"""
            def apply():
                (logits, mtp_logits), sown = self.model.apply(
                    {"params": p}, x, ahead, method="logits_and_mtp_logits",
                    mutable=["routing"])
                return logits, mtp_logits, {
                    layer: entry["moe"]["chosen"][0]
                    for layer, entry in sown.get("routing", {}).items()}

            deployed = apply()
            with jax.default_matmul_precision("highest"):
                return deployed, apply()

        def keep(compile_one):
            try:
                compile_one()
            except BaseException as e:  # handed to whoever asks for the programs
                self.failed = e

        def compile_system():
            self.system = jax.jit(system).lower(params, self.ids, self.ids).compile()

        def compile_reference():
            self.plain = self.reference.compile_forward_given_routing(
                params, self.ids, self.sizes, mtp=True)

        try:
            params = jax.eval_shape(
                lambda x: self.model.init(jax.random.PRNGKey(0), x)["params"],
                self.ids)
        except BaseException as e:
            self.failed = e
            return
        beside = threading.Thread(target=keep, args=(compile_reference,),
                                  name="comparisons-ahead-reference")
        beside.start()
        keep(compile_system)
        beside.join()


def reference_checks(cell: Cell, ahead: BothHeadsAhead, params,
                     ids: np.ndarray) -> dict[str, dict]:
    """Both heads' logits for the inputs ``ids[:, :-1]`` and the tokens
    after them ``ids[:, 1:]`` against the plain reference's, as deployed and
    with the system under ``highest``, the reference given the experts the
    system chose in that pass; and that choice against the reference's own
    scores, the main layers' and the module's block's apart (module
    docstring)."""
    system, plain_forward = ahead.programs()
    inputs = np.ascontiguousarray(ids[:, :-1])
    after = np.ascontiguousarray(ids[:, 1:])
    checks = {}
    for suffix, (logits, mtp_logits, routing) in zip(
            ("", "_float32"), system(params, inputs, after)):
        routing = {layer: np.asarray(chosen) for layer, chosen in routing.items()}
        if MTP_LAYER not in routing or len(routing) < 2:
            checks["routing" + suffix] = {
                "ok": False, "why": "the model sowed no choice of experts for "
                f"the main layers and the module's block: {sorted(routing)}"}
            continue
        (plain, plain_mtp), (margin, mtp_margin) = plain_forward(
            params, inputs, routing, next_ids=after)
        checks["reference_logits" + suffix] = win.logits_agreement(
            np.asarray(logits), plain,
            float(cell.config["logit_tolerance" + suffix]))
        checks["reference_mtp_logits" + suffix] = win.logits_agreement(
            np.asarray(mtp_logits), plain_mtp,
            float(cell.config["mtp_logit_tolerance" + suffix]))
        limit = float(cell.config["routing_margin_tolerance" + suffix])
        main_layers = sorted(set(routing) - {MTP_LAYER})
        for name, value, layers in (("routing", margin, main_layers),
                                    ("routing_mtp", mtp_margin, [MTP_LAYER])):
            checks[name + suffix] = {
                "ok": value <= limit, "margin": value, "layers": layers,
                "compared": win.compared(value, "<=", limit)}
    return checks


@contextlib.contextmanager
def _in_place_of(module, **names):
    before = {name: getattr(module, name) for name in names}
    for name, value in names.items():
        setattr(module, name, value)
    try:
        yield
    finally:
        for name, value in before.items():
            setattr(module, name, value)


def run(cell: Cell, devices: list, start_wall: float) -> Observed:
    with _in_place_of(train_tokens, ComparisonsAhead=BothHeadsAhead,
                      reference_checks=reference_checks):
        return train_tokens.run(cell, devices, start_wall)
