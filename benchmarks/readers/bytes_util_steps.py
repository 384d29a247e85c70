"""``bytes_util`` for a decode executable whose step carries several
sequences (the images of one request): bytes the traced requests' decode
STEPS needed over what HBM could have moved while the decode executable was
busy, in per cent. ``module`` is the executable, ``needs`` the file of
``harness/`` that counts a step's bytes from shapes (``decode_bytes(cfg,
first position, steps, distinct experts read a step, sequences)``).

What a step read comes from the program's own counters, window-wide
(``serving.expander``): ``experts_read`` over ``decode_steps`` is the
distinct held experts a step's rows chose, summed over the expert layers
(NOT ``expert_tokens``, the picks: an expert is streamed once however many
rows chose it), and ``tokens_decoded`` over ``decode_steps`` the sequences
a step carried. The steps a request's decode executable ran and where they
started are read from its script's arguments and its prompt (the hash
tokenizer makes one token a word). A program that decodes the images one
after the other runs ``batch_size`` times these steps in the same
executable: the share then reads low, never high. A program without the
counters, a slice without the executable: nothing to read."""


PATH = ["serving", "expander"]


def read(context: dict, module: str, needs: str, steps_per_call: int):
    trace = context.get("trace")
    traced = [r for r in context["records"] if r.traced]
    cfg = getattr(context["family"], "expander", None)
    try:
        busy = trace["modules"][module]
    except (KeyError, TypeError):
        return None
    # the two quotients are ``status_ratio``'s: the counters' growth over
    # the window, None where the program has no such counter
    ratio = context["bench"].load("readers", "status_ratio").read
    read_per_step = ratio(context, PATH, ["experts_read"], ["decode_steps"])
    sequences = ratio(context, PATH, ["tokens_decoded"], ["decode_steps"])
    if (cfg is None or not traced or busy <= 0 or read_per_step is None
            or sequences is None):
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
                                     read_per_step, sequences)
    capacity = busy * context["chips"] * context["peak"]["hbm_bytes_per_s"]
    return 100.0 * needed / capacity
