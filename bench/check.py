"""How ``correct`` is decided: what the window served, against the
configuration's plain reference (``bench/references/<config>.py``).

- One-shot cells: a sample, drawn from the seed, of ``correct.sample``
  requests the window finished.  The number compared is the largest, over
  the sample, of ``max|served - reference| / max|reference|`` per request.
- Decode cells: the session that served most tokens and
  ``correct.sessions - 1`` others drawn from the seed.  The reference runs
  once over each one's prompt and served tokens; at every served token the
  gap is the reference's best logit minus the reference's logit of the
  served token (0 where greedy decoding agrees).  The number compared is
  the widest gap.

A control puts the reference itself, computed in a lower precision
(``bfloat16``, or ``int8`` operands; the configuration's ``correct.control``
names the one its limit is held against), in the program's place: for
one-shot cells its outputs are compared as the served ones are; for decode
cells the gap is read at the token the lower precision puts first, over
the same prompts and served tokens.  The benchmark's runs never compute
it; ``bench/calibrate.py`` and the tests do.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import load, spec, weights


@dataclasses.dataclass
class Verdict:
    passed: bool
    compared: dict           # name -> {"value": ..., "limit": ...}
    program: float
    controls: dict           # control precision -> reading


def _params(win):
    graph = spec.model_module(win.config).build_graph(win.config)
    return weights.init_params(weights.param_specs(graph), win.seed)


def oneshot_readings(win, params, controls) -> tuple[float, dict]:
    ref_mod = spec.reference_module(win.config["name"])
    done = [r for r in win.records if r.error is None]
    n = min(int(win.config["correct"]["sample"]), len(done))
    if n == 0:
        return float("inf"), {}
    pick = load.seed_rng(win.seed, 3).choice(len(done), n, replace=False)
    reqs = [done[i] for i in sorted(pick)]
    pool = win.driver.pool
    images = np.concatenate([pool[r.index] for r in reqs])
    served = np.concatenate([r.output for r in reqs]).astype(np.float32)
    ref = ref_mod.logits(params, win.config, images)

    def worst(out):
        err = np.abs(out - ref).max(axis=1)
        return float((err / np.abs(ref).max(axis=1)).max())

    return worst(served), {
        c: worst(ref_mod.logits(params, win.config, images, c))
        for c in controls}


def decode_readings(win, params, controls) -> tuple[float, dict]:
    import jax.numpy as jnp
    ref_mod = spec.reference_module(win.config["name"])
    done = [s for s in win.records if s.error is None and s.tokens]
    if not done:
        return float("inf"), {}
    longest = max(range(len(done)), key=lambda i: len(done[i].tokens))
    others = [i for i in range(len(done)) if i != longest]
    k = min(int(win.config["correct"]["sessions"]) - 1, len(others))
    pick = [longest] + sorted(load.seed_rng(win.seed, 4).choice(
        others, k, replace=False).tolist() if k else [])
    prog, ctrl = 0.0, {c: 0.0 for c in controls}
    for i in pick:
        s = done[i]
        prompt = win.driver.prompts[s.prompt]
        seq = np.concatenate([prompt, np.asarray(s.tokens[:-1], np.int32)])
        rows = slice(len(prompt) - 1, None)
        ref = ref_mod.logits(params, win.config, seq)[rows]
        best = ref.max(axis=-1)
        at = jnp.arange(ref.shape[0])
        served = jnp.asarray(s.tokens, jnp.int32)
        prog = max(prog, float((best - ref[at, served]).max()))
        for c in controls:
            low = ref_mod.logits(params, win.config, seq, c)[rows]
            ctrl[c] = max(ctrl[c],
                          float((best - ref[at, low.argmax(-1)]).max()))
    return prog, ctrl


READINGS = {"oneshot": oneshot_readings, "decode": decode_readings}


def check(win, controls: tuple[str, ...] = ()) -> Verdict:
    """The verdict on what ``win`` served, with the readings of each
    control precision in ``controls`` (``bfloat16``, ``int8``)."""
    corr = win.config["correct"]
    params = _params(win)
    prog, ctrl = READINGS[win.traffic["kind"]](win, params, controls)
    limit = float(corr["limit"])
    compared = {corr["number"]: {"value": prog, "limit": limit}}
    return Verdict(bool(prog <= limit), compared, prog, ctrl)
