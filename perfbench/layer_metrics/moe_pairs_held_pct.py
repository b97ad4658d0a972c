"""Routed experts, a chip's share: of the (token, choice) pairs the router
made over the window, the share whose expert is held here — ``stats()``
``moe_pairs_routed_total`` (pairs given to experts held, which is what every
other ``moe.*`` metric counts) over that plus ``moe_pairs_elsewhere_total``
(pairs of experts held on other chips, which this chip leaves out). 16 of 256
experts under an even router read 6.25; a selection bias makes it uneven. A
program that holds every expert it routes over has no second counter and
reads ``None``."""


def read(name: str, lc: dict):
    s0, s1 = lc.get("stats0") or {}, lc.get("stats1") or {}
    keys = ("moe_pairs_routed_total", "moe_pairs_elsewhere_total")
    if any(k not in s0 or k not in s1 for k in keys):
        return None
    held, elsewhere = (float(s1[k]) - float(s0[k]) for k in keys)
    return 100.0 * held / (held + elsewhere) if held + elsewhere > 0 else None
