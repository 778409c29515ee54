"""95th percentile of the gaps between consecutive output tokens (the
end-to-end definition, read in the traced run): the steps that prefill and
stage a long prompt set it, so it swings with how they fall."""


def read(rec):
    return rec.get("e2e", {}).get("itl_p95_ms")
