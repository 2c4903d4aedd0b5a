"""1 - (union of the intervals in which an operation ran on the device)
/ the traced window, mean over the chips used."""


def read(run):
    t = run.get("trace")
    if not t:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
