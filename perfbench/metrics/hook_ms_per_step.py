"""Host milliseconds inside ``GuardStepHook.__call__`` per step of the
window, including ``GuardSession.observe`` on window-closing steps
(span ``guard_hook``, around each call)."""


def read(result, cfg, device_kind):
    return result["layer"]["hook_ms_per_step"]
