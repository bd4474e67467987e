"""Device time a round of the forward pass the recomputation blocks make again
for the backward they serve: the matched ops the program's report flags
`recomputed` (`obs.device.scope_of`: inside `tau_step` on a `transpose(`
path, under `rematted_computation`). By construction a part of
`step_backward_ms`. What a kernel's own backward makes again inside itself,
and a recomputed elementwise op fused behind a product of the backward pass
proper, is not in it. None where no op of the report carries the key (a
program from before it had one): a sum over nothing would read 0 there.

The run note gets the same time by layer and scope
(`recompute_by_layer_ms`: `[[layer type / layer (the innermost scope of an
op under no layer, as `scope_math.by_layer` names it), the named scope
directly under the layer ("" where none), ms of the ops that hold a product
or are a kernel call, ms of the rest]]`, the `ROWS` longest and one row
`other`; `recompute_by_type_ms`: the same by layer TYPE and scope, every
row -- a net's layers of one type read alike, and forty rows by layer leave
a quarter of Nemotron's time under `other`) and beside it what the blocks
keep (`recompute`: the report's part of that name, `{kept name: {"forward",
"backward", "kept_bytes"}}`): milliseconds made
again next to bytes kept."""
from __future__ import annotations

ROWS = 40


def holds_product(op) -> bool:
    """The part a kept name can remove (`obs.device.recompute_report`'s
    test): the op holds a product, or is a kernel call."""
    return bool(op.get("matmul")) or op.get("opcode") == "custom-call"


def made_again(run, keep=lambda op: True):
    """Milliseconds a traced round of the recomputed ops `keep` accepts, or
    None (module docstring)."""
    sm = run.ctx.load("scope_math.py")
    j = sm.joined(run)
    if j is None or not any("recomputed" in op for _, _, op in j["matched"]):
        return None
    return sm.sum_ms(run, lambda op: op.get("recomputed") and keep(op))


def under_layer(op) -> str:
    """The named scope directly under the op's layer ("" where the next
    component of its path is a transform's or there is none)."""
    parts = op["scope"].split("/")
    for i in range(len(parts) - 2):
        if parts[i:i + 2] == [op["layer_type"], op["layer"]]:
            return "" if "(" in parts[i + 2] or ")" in parts[i + 2] else parts[i + 2]
    return ""


def table(matched, name_of, rows=None) -> list:
    """`[[name_of(op), the scope under its layer, ms of products and kernels,
    ms of the rest]]` of the recomputed ops of `matched` ([(name, ms a round,
    report entry)]), longest first; past `rows` of them, one row `other`."""
    total: dict = {}
    for _, ms, op in matched:
        if op.get("recomputed"):
            row = total.setdefault((name_of(op), under_layer(op)), [0.0, 0.0])
            row[0 if holds_product(op) else 1] += ms
    longest = sorted(total.items(), key=lambda kv: -sum(kv[1]))
    out = [[*key, *row] for key, row in longest[:rows]]
    if rows is not None:
        out.append(["other", "", *(sum(r[i] for _, r in longest[rows:])
                                  for i in (0, 1))])
    return out


def layer_of(op) -> str:
    """As `scope_math.by_layer` names an op's layer."""
    return (f'{op["layer_type"]}/{op["layer"]}' if op["layer"]
            else op["scope"].rsplit("/", 1)[-1] or "-")


def read(run):
    total = made_again(run)
    if total is None:
        return None
    sm = run.ctx.load("scope_math.py")
    matched = sm.joined(run)["matched"]
    run.notes["recompute_by_layer_ms"] = table(matched, layer_of, ROWS)
    run.notes["recompute_by_type_ms"] = table(
        matched, lambda op: op["layer_type"] or "-")
    run.notes["recompute"] = {
        name: {k: part.get(k) for k in ("forward", "backward", "kept_bytes")}
        for name, part in ((sm.report()[0] or {}).get("recompute") or {}).items()}
    return total
