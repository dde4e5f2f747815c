"""xla_share.train: device time outside the Pallas kernels over all
device time, in %, in the training cells: rounding bits, quantize
reductions, the jnp-routed products and the rest of XLA's own work."""


def read(rec):
    if rec["traffic"]["kind"] != "train" or not rec["trace"]:
        return None
    fam = rec["trace"]["family_s"]
    total = sum(fam.values())
    return 100.0 * fam.get("xla", 0.0) / total if total > 0 else None
