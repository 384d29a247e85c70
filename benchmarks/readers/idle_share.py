"""Share of the traced slice in which no operation ran on the device, in
per cent; the mean over the cell's devices."""


def read(context: dict):
    trace = context["trace"]
    if not trace or context["slice_s"] <= 0 or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / context["slice_s"])
