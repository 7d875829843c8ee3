"""Top-k kernel (ops/pallas_topk.py): share of the 128-item chunks the
kernel walked that its threshold gate let through to the sort network, in
percent: delta `oryx_topk_chunks_folded` over delta `oryx_topk_chunks`
across the window. It rises with the real rows a dispatch and with item
order (ascending scores fold every real chunk). A program without the
counters, and a path that is not the fused kernel, give nothing."""


def read(src):
    c = src.get("counters") or {}
    walked = c.get("oryx_topk_chunks", 0.0)
    return c.get("oryx_topk_chunks_folded", 0.0) / walked * 100.0 if walked else None
