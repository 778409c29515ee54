"""Model FLOPs of every prompt token prefilled and every output token
decoded in the window (counted from shapes by the configuration's model
family, ``families/<family>.py``) over the window times the chip's bf16
peak."""


def read(rec):
    if rec.get("peaks") is None:
        return None
    fl, w = rec.get("model_flops", 0.0), rec.get("window_s", 0.0)
    if not fl or not w:
        return None
    return 100.0 * fl / (w * rec["peaks"]["bf16_flops"])
