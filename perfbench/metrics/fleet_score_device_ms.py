"""Device milliseconds of the ``fleet_score`` kernel's events per frame
of the traced window."""


def read(result, cfg, device_kind):
    tr = result["trace"]
    if not tr or not tr["kernel_events"].get("fleet_score"):
        return None
    return 1e3 * tr["kernel_s"]["fleet_score"] / result["layer"]["frames"]
