"""The prefix's share of a step's device time, %: device seconds of the
program ``eval_prefill`` (the encoder pass the ``Evaluator`` runs as a
program of its own under ``eval.prefill_program``) over those of it and of
the job's ``eval_decode`` program (the beam search from its output) in the
traced stretch. A program that runs both as one (no ``eval_prefill`` module
in the trace) has nothing to read."""

import re

PREFILL = re.compile(r"^jit_eval_prefill$")


def read(reading):
    tr = reading["trace"]
    pattern = reading["result"].get("modules", {}).get("eval_decode")
    if tr is None or not pattern:
        return None
    decode = re.compile(pattern)
    shares = []
    for d in tr["devices"]:
        pre = sum(v for k, v in d["module_s"].items() if PREFILL.search(k))
        dec = sum(v for k, v in d["module_s"].items() if decode.search(k))
        if pre and dec:
            shares.append(100.0 * pre / (pre + dec))
    return sum(shares) / len(shares) if shares else None
