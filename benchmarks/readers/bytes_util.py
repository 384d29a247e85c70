"""Bytes the decoded tokens of the traced slice needed, over what HBM could
have moved while the decode executable was busy: a utilisation over busy
time, in per cent. ``module`` is the executable, ``needs`` the file of
``harness/`` that counts a token's bytes from shapes (``decode_bytes``).
How many of its chosen experts a token found held here comes from the
program's own counter (``serving.expander.expert_tokens`` over the tokens
that passed the expert layers, window-wide). The steps a request's decode
executable ran and where they started are read from its script's arguments
and its prompt (the hash tokenizer makes one token a word). A program
without the counter, a slice without the executable: nothing to read."""


def _delta(context: dict, key: str):
    before = context["status_before"]["serving"]["expander"][key]
    after = context["status_after"]["serving"]["expander"][key]
    if isinstance(after, list):
        return sum(map(sum, after)) - sum(map(sum, before))
    return after - before


def read(context: dict, module: str, needs: str, steps_per_call: int):
    trace = context.get("trace")
    traced = [r for r in context["records"] if r.traced]
    cfg = getattr(context["family"], "expander", None)
    try:
        busy = trace["modules"][module]
        routed = _delta(context, "expert_tokens")
        passed = (_delta(context, "tokens_prefilled")
                  + _delta(context, "decode_steps"))
    except (KeyError, TypeError):
        return None
    if cfg is None or not traced or busy <= 0 or passed <= 0:
        return None
    count = context["bench"].load("harness", needs)
    needed = 0.0
    for record in traced:
        scripts = {k.lower(): v for k, v in
                   record.payload.get("alwayson_scripts", {}).items()}
        args = scripts["prompt expansion"]["args"][0]
        start = (1 + len(args["instruction"].split())
                 + len(record.payload["prompt"].split()))
        calls = -(-(int(args["max_new_tokens"]) - 1) // steps_per_call)
        needed += count.decode_bytes(cfg, start, calls * steps_per_call,
                                     routed / passed)
    capacity = busy * context["chips"] * context["peak"]["hbm_bytes_per_s"]
    return 100.0 * needed / capacity
