"""Host milliseconds per step scoring the rollouts and forming the advantage
(the program's ``rl.reward.score`` spans, a part of ``rl.reward``). The
read-back that ends the wait for the decode and tracing's own decode
accounting (``rl.reward.observe``) are not in it."""

from benchmark.layer_metrics import _spans


def read(reading):
    return _spans.ms_per_step(reading, "rl.reward.score")
