"""A sum of numbers from one block of /internal/status as the run read it
(``status_before``: after warm-up, before the window; ``status_after``:
after it). ``path`` walks down to the block, ``keys`` names the numbers
added up. A program whose status lacks the block or a key gives None."""


def read(context: dict, status: str, path: list[str], keys: list[str]):
    block = context.get(status)
    for step in path:
        if not isinstance(block, dict) or step not in block:
            return None
        block = block[step]
    if not isinstance(block, dict) or any(
            not isinstance(block.get(key), (int, float)) for key in keys):
        return None
    return float(sum(block[key] for key in keys))
