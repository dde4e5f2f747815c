"""train_gemm_roofline: the least time of the train step's projection
and head GEMMs routed to a Pallas kernel, over the device time of the
GEMM kernels, in %.

The step's GEMMs come from the configuration: each layer's q, k, v, o,
gate, up and down projections and the tied head, forward (kind qq),
input gradient (qi) and weight gradient (ii), once each per step (the
rematerialized forward is not counted).  ``record_decisions()`` of the
first step says which route each shape took; only kernel-routed ones
count.  A GEMM's least time is the larger of its operations over the int8
peak and its least bytes over the HBM bandwidth (``ops.least_seconds``).
The kernels' time is that of every operation ``kernel_names.json`` puts
in the ``gemm`` family, attention's batched products among them, so the
share is of required projection work and can only read low.
"""

import sys

from bench import ops

KERNEL_ROUTES = ("fused", "unfused")


def read(rec):
    if rec["traffic"]["kind"] != "train" or not rec["trace"]:
        return None
    kernel_s = rec["trace"]["family_s"].get("gemm", 0.0)
    routes = {(d["op"], d["m"], d["k"], d["n"]): d["path"]
              for d in rec["run"]["decisions"]}
    peaks = rec["peaks"]
    tokens = rec["run"]["batch"] * rec["run"]["seq"]
    least, bound = 0.0, {"ops": 0.0, "bytes": 0.0}
    for op, m, k, n, kind, count in ops.step_gemms(rec["config"], tokens):
        if routes.get((op, m, k, n)) not in KERNEL_ROUTES:
            continue
        t, which = ops.least_seconds(m, k, n, kind, peaks["int8_ops_per_s"],
                                     peaks["hbm_bytes_per_s"])
        least += count * t
        bound[which] += count * t
    if least == 0.0 or kernel_s <= 0.0:
        return None
    steps = rec["window"]["steps"]
    print(f"[train_gemm_roofline] least {least!r} s/step over {steps} steps "
          f"against {kernel_s!r} s of gemm kernels; bound by ops "
          f"{bound['ops'] / least:.3f}, by bytes {bound['bytes'] / least:.3f}",
          file=sys.stderr)
    return 100.0 * least * steps / kernel_s
