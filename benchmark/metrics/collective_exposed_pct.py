"""Share of the traced steps during which a collective ran on a device
and no compute did (mean over the devices)."""


def read(run):
    t = run.get("trace")
    if not t:
        return None
    return t["collective_exposed_s"] / t["window_s"] * 100.0
