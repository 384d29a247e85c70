"""``status_value`` times ``scale``: a sum of numbers from one block of
/internal/status in another unit (bytes of a cache as MiB a position: the
divisor is the traffic's, written in the metric's file). A program whose
status lacks the block or a key gives None."""


def read(context: dict, status: str, path: list[str], keys: list[str],
         scale: float):
    value = context["bench"].load("readers", "status_value").read(
        context, status, path, keys)
    return None if value is None else scale * value
