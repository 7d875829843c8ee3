"""ALS serving model: `oryx_post_stage_seconds{stage="render"}`, mean per
answer over the window: the payload bytes (`_render_body` in
serving/app.py). The third part of `serialize` (post_ms_per_req)."""

from benchmarks import timeline


def read(src):
    return timeline.post_stage_ms(src, "render")
