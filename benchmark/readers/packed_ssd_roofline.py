"""The scan kernels' share of their roofline on rows that hold several
documents: the least time the chip could take for the recurrence's products
and for moving its operands, results and chunk states
(packed_ssm_lm_flops.py, peaks.json) over the time the Pallas kernel calls
under scope `Mamba2/*/ssd` took in the device trace (`ssd_chunk_fwd`, made a
second time with its chunk states for the backward, and `ssd_chunk_bwd`: the
second forward counts in the time and not in the operations or bytes). The
count reads the same whatever chunk and head block the kernels walk and
wherever the documents begin; which they walk is the report's own `ssd` part
(kernel calls, chunk, heads a program), put into the run note. Nothing to
read in a program whose scans ran no kernel."""
from __future__ import annotations


def read(run):
    sm = run.ctx.load("scope_math.py")
    ms = sm.sum_ms(run, lambda op: op["layer_type"] == "Mamba2"
                   and op.get("pallas")
                   and "/ssd/" in "/" + op["scope"] + "/")
    if not ms:
        return None
    packed, flops = run.ctx.load("packed_ssm_lm_flops.py"), run.ctx.load("flops.py")
    c = run.ctx.config
    cost = packed.ssd_step_cost(
        run.ctx.reference.layer_table(c), c["local_batch"], c["seq_len"],
        4 if c["precision"] == "float32" else 2)
    share, bound = flops.roofline_share(cost["ops"] * c["tau"], cost["bytes"] * c["tau"],
                                        1e-3 * ms, flops.peaks(run.device_kind))
    run.notes["packed_ssd_roofline_bound"] = bound
    run.notes["ssd"] = (sm.report()[0] or {}).get("ssd")
    run.notes["packed_ssd_kernels_ms"] = ms
    return share
