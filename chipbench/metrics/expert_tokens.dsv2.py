"""(token, slot) pairs that reached a held expert, per held expert, per
MoE layer, per decode step of the window: the load each of this chip's
experts sees (a program counter summed on the device)."""


def read(r):
    c = r.counters
    per = c.get("steps", 0) * c.get("held_experts", 0) * c.get("moe_layers",
                                                               0)
    if not per or "held_pairs" not in c:
        return None
    return c["held_pairs"] / per
