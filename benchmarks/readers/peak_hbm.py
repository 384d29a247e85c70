"""Peak bytes in use on the fullest of the cell's devices, in GiB."""


def read(context: dict):
    peak = max((m.get("peak_bytes_in_use", 0) for m in context["memory"]),
               default=0)
    return peak / 2 ** 30 if peak else None
