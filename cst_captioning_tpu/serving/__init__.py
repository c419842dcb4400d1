"""Always-on caption serving: continuous batching over the decode stack.

The decode endgame (PRs 4-5) built a fast offline rollout program: fused
(1+K)-lane scan, multi-step stride kernel, finished-lane compaction. This
package productionizes it into a request-serving layer (README "Serving"):

- :mod:`serving.pages`   — paged HBM bank for ragged encoder outputs
  (fixed-size pages + host free-list + device page table, replacing
  per-request padded slabs — the Ragged Paged Attention memory layout);
- :mod:`serving.engine`  — :class:`CaptionService`: request queue +
  admission/batch-former loop slotting new clips into decode lanes freed
  between strides (continuous batching), with drain/snapshot/restore for
  preemption;
- :mod:`serving.traffic` — seeded, replayable Poisson/bursty traffic traces.

Every request decodes on its OWN fold_in RNG stream, so a request admitted
mid-flight is token- and logprob-bit-identical to the same clip decoded
offline through decoding/fused.py (pinned by tests/test_serving.py).

The engine is also the ONLINE RL actor (README "Online RL from served
traffic"): a ``feedback`` hook hands every completed request's lanes to
:class:`~cst_captioning_tpu.rl.online.OnlineSCSTTrainer`, and
:meth:`CaptionService.publish_params` hot-swaps learner params back in at
a stride boundary — drain-free, with in-flight requests pinned to their
admission-time param version (still bit-identical to the offline decode
under that version).
"""

from cst_captioning_tpu.serving.engine import (
    CaptionResult,
    CaptionService,
    ClipRequest,
    ServeReport,
    load_snapshot,
    request_drain,
)
from cst_captioning_tpu.serving.pages import OutOfPages, PageBank
from cst_captioning_tpu.serving.traffic import Trace, TrafficSpec, make_trace

__all__ = [
    "CaptionResult",
    "CaptionService",
    "ClipRequest",
    "OutOfPages",
    "PageBank",
    "ServeReport",
    "Trace",
    "TrafficSpec",
    "load_snapshot",
    "make_trace",
    "request_drain",
]
