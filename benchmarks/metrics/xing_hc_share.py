"""Hyper-connections (ops/xing.py): the device time under the `xing.hc` scope
(every sublayer's maps: the streams' norm, the maps' product at highest
precision, the clamp, exp and the 20 Sinkhorn iterations; the weighted sum
into the sublayer and the mix and write back, in both programs) as a share of
the xing programs' device time in the traced window. The other scopes'
shares go to stderr."""

import sys

from benchmarks.metrics import _xing


def read(src):
    share = _xing.scope_share(src, "xing.hc")
    if share is None:
        return None
    steps = src["steps"]
    for s in sorted({s for p in steps.values() for s in p["scoped"]}):
        print(f"xing_hc_share: {s}: {_xing.scope_share(src, s) or 0.0:.1f} % of the xing programs' device time",
              file=sys.stderr)
    return share
