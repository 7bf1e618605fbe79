"""Host milliseconds inside ``TieredPolicy.decide`` per frame of the
window (span ``policy.decide``)."""


def read(result, cfg, device_kind):
    return result["layer"]["policy_ms_per_frame"]
