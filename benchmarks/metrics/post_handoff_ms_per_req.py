"""ALS serving model: `oryx_post_stage_seconds{stage="handoff"}`, mean per
answer over the window: results on the host until `_post` starts on a
post-pool thread. The first of the three parts of `serialize`
(post_ms_per_req); it is queueing behind the dispatch's other answers, not
work.

Also prints, on stderr, the parts against the whole: handoff + rerank +
render, `post_ms_per_req`, and the residue (the future's callback hop
between `_post` returning and rendering starting)."""

import sys

from benchmarks import timeline
from benchmarks.metrics import post_ms_per_req


def read(src):
    parts = {stage: timeline.post_stage_ms(src, stage) for stage in ("handoff", "rerank", "render")}
    whole = post_ms_per_req.read(src)
    if None not in parts.values() and whole is not None:
        total = sum(parts.values())
        print(
            "post_handoff_ms_per_req: "
            + " + ".join(f"{stage} {ms:.3f}" for stage, ms in parts.items())
            + f" = {total:.3f} ms of post_ms_per_req {whole:.3f}; residue {whole - total:.3f} ms",
            file=sys.stderr,
        )
    return parts["handoff"]
