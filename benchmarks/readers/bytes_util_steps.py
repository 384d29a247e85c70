"""Bytes the traced requests' decode STEPS needed over what HBM could have
moved while the decode executable was busy: a utilisation over busy time,
in per cent, for a step of one sequence or of several (the images of one
request). ``module`` is the executable, ``needs`` the file of ``harness/``
that counts a step's bytes from shapes (``decode_bytes(cfg, first position,
steps, distinct experts read a step, sequences, first_step=)``):
``bytes_lm`` walks any ``LMConfig``, so every configuration's roofline is
this one reader over that one file.

What a step read comes from the program's own counters, window-wide
(``serving.expander``): ``experts_read`` over ``decode_steps`` is the
distinct held experts a step's rows chose, summed over the expert layers
(NOT ``expert_tokens``, the picks: an expert is streamed once however many
rows chose it), and ``tokens_decoded`` over ``decode_steps`` the sequences
a step carried. At one sequence a step both quotients are what the reader
this one replaced (``bytes_util``, PR 58) took from ``expert_tokens``: a
step of one token reads as many experts as it has picks held, and
``experts_read`` counts exactly those (pipeline/expand.py:account), over
the decode steps alone where the picks were averaged over the prefilled
tokens too. The steps a request's decode executable ran and where they
started are read from its script's arguments and its prompt (the hash
tokenizer makes one token a word). A program that decodes the images one
after the other runs ``batch_size`` times these steps in the same
executable: the share then reads low, never high.

The share is of the launches THE TRACE KEPT. A launch of ``module`` runs
``steps_per_call`` steps, and the seconds below are summed over the
launches the profiler's trace holds (``trace["module_calls"]``). A trace
that came back short (a host stall inside the slice: PR 58 lost one launch
of twelve in one run of 38, and the requests' whole steps over eleven
launches' seconds read 97.5 for 89.3) lacks the launch on both sides here:
the bytes are those of as many launches as the trace holds, the CHEAPEST of
the traced requests' (a launch further on attends more rows), so the share
is under what the kept launches moved whichever were lost, and with every
launch there it is the requests' whole count. More launches than the
requests' arguments give (a program that decodes the images one after the
other; another request's launch inside the slice) count no more bytes: low,
never high. A program without the counters, a slice without the
executable: nothing to read."""


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
    launches = []       # the bytes of each launch the requests asked for
    for record in traced:
        scripts = {k.lower(): v for k, v in
                   record.payload.get("alwayson_scripts", {}).items()}
        args = scripts["prompt expansion"]["args"][0]
        start = (1 + len(args["instruction"].split())
                 + len(record.payload["prompt"].split()))
        calls = -(-(int(args["max_new_tokens"]) - 1) // steps_per_call)
        launches += [count.decode_bytes(cfg, start, steps_per_call,
                                        read_per_step, sequences,
                                        first_step=call * steps_per_call)
                     for call in range(calls)]
    # launches on every device of a mesh are one launch of the program
    kept = trace.get("module_calls", {}).get(module, 0) // max(
        1, len(trace.get("devices", {})))
    needed = sum(sorted(launches)[:kept])
    if not needed:
        return None
    capacity = busy * context["chips"] * context["peak"]["hbm_bytes_per_s"]
    return 100.0 * needed / capacity
