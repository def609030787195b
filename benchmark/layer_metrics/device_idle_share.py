"""1 - device busy time over the traced window, in percent."""


def read(run):
    share = (run.get("trace") or {}).get("idle_share")
    return None if share is None else 100.0 * share
