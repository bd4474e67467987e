"""Device time a round of the Mamba-2 mixers of a model whose rows hold several
documents (type `Mamba2`: the two projections, the taps and the scan under
document runs, the gate and its norm over all channels), both passes, the
recomputed forward with them (scope_math.py): `mamba_device_ms`'s reading,
under a name of the packed cell's own (that metric's list is the state-space
cell's alone). The run note gets the same time by the mixer's own scopes
(`packed_mamba_by_scope_ms`: `in_proj`, `conv`, `ssd`, `gate_norm`,
`out_proj`, and what lies under none of them)."""
from __future__ import annotations

SCOPES = ("in_proj", "conv", "ssd", "gate_norm", "out_proj")


def read(run):
    sm = run.ctx.load("scope_math.py")
    total = sm.layer_type_ms(run, "Mamba2")
    if not total:
        return None
    by_scope: dict = {}
    for _, ms, op in sm.joined(run)["matched"]:
        if op["layer_type"] == "Mamba2":
            parts = op["scope"].split("/")
            part = next((s for s in SCOPES if s in parts), "other")
            by_scope[part] = by_scope.get(part, 0.0) + ms
    run.notes["packed_mamba_by_scope_ms"] = by_scope
    return total
