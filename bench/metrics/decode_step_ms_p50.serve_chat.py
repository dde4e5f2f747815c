"""decode_step_ms_p50.serve_chat: the median host wall time, in ms, of
the ``Engine.step()`` calls of the traced window that admitted nothing:
one batched decode step with its host cache round trip."""


def read(rec):
    return rec["window"].get("decode_step_ms_p50")
