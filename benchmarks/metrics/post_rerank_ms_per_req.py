"""ALS serving model: `oryx_post_stage_seconds{stage="rerank"}`, mean per
answer over the window: `_post` in apps/als/serving.py (capacity-pad
filter, exact f32 re-rank, trim and id translation, shadow-sample
enqueue). The second part of `serialize` (post_ms_per_req)."""

from benchmarks import timeline


def read(src):
    return timeline.post_stage_ms(src, "rerank")
