"""The head inside the step (ops/seq.py catalog_head behind ops/trinity.py):
the device time under the `trinity.head` scope (the final norm, the logits
over the served view's rows, the argmax) as a share of the trinity programs'
device time in the traced window. The view has `row_capacity` rows for the
catalog's items: what the head walks past the items is in this share."""


def read(src):
    steps = src.get("steps")
    if not steps:
        return None
    seconds = sum(p["seconds"] for p in steps.values())
    head = sum(p["scoped"].get("trinity.head", 0.0) for p in steps.values())
    return head / seconds * 100.0 if seconds and head else None
