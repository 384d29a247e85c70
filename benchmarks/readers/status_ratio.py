"""A quotient of two sums of counters of one block of /internal/status,
each the counter's growth over the window (``status_after`` less
``status_before``: after warm-up, before the window; after it), times
``scale``. ``path`` walks down to the block; ``over`` and ``under`` name
the counters summed above and below the line. A program whose status lacks
the block or a counter, or a window in which the counters below the line
did not move, gives None.

``per`` names a tuple of the expander's ``LMConfig`` (``expert_layers``)
and divides the quotient by its length: a count summed over the layers of
one kind becomes a count a layer, in every configuration by its own number
of such layers and with no literal in the metric's file. A family without
an expander, or with none of those layers, gives None."""


def _block(context: dict, status: str, path: list[str]):
    block = context.get(status)
    for step in path:
        if not isinstance(block, dict) or step not in block:
            return None
        block = block[step]
    return block if isinstance(block, dict) else None


def read(context: dict, path: list[str], over: list[str],
         under: list[str], scale: float = 1.0, per: str | None = None):
    if per is not None:
        cfg = getattr(context.get("family"), "expander", None)
        layers = len(getattr(cfg, per, ()))
        if not layers:
            return None
        scale = scale / layers
    before = _block(context, "status_before", path)
    after = _block(context, "status_after", path)
    if before is None or after is None:
        return None
    if any(not isinstance(block.get(key), (int, float))
           for block in (before, after) for key in over + under):
        return None
    grown = [sum(after[key] - before[key] for key in keys)
             for keys in (over, under)]
    return scale * grown[0] / grown[1] if grown[1] else None
