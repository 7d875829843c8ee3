"""Expert layer, kind trinity-serving: share of the routed (token, expert)
pairs that an expert held HERE computed, in percent: delta
`oryx_moe_routed_total` over it plus delta `oryx_moe_routed_elsewhere_total`
(the pairs whose expert lies on another chip of the layer: counted, never
computed, never dropped). 12.5 % where the router is even over 256 experts of
which 32 are held."""


def read(src):
    c = src.get("counters") or {}
    here = c.get("oryx_moe_routed_total", 0.0)
    everywhere = here + c.get("oryx_moe_routed_elsewhere_total", 0.0)
    return here / everywhere * 100.0 if here and everywhere else None
