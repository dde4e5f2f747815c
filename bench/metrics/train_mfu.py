"""train_mfu: the train step's share of the chip's int8 peak, in %.

Operations one token of training requires (``ops.train_ops_per_token``:
6 per GEMM weight, tied head included, and causal attention; no
recomputation) times the traced window's tokens per second, over the
int8 peak of ``peaks.json``.
"""

from bench import ops


def read(rec):
    if rec["traffic"]["kind"] != "train":
        return None
    per_token = ops.train_ops_per_token(rec["config"], rec["traffic"]["seq"])
    rate = rec["window"]["train_tokens_per_s"]
    return 100.0 * per_token * rate / rec["peaks"]["int8_ops_per_s"]
