"""Steps from the fault's onset to Guard's restart request, both
counted. None where the fault was missed."""


def read(result, cfg, device_kind):
    return result["layer"].get("detect_steps")
