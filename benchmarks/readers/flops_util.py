"""UNet operations the traced slice's images need, over what the device
could have done while it was busy: a utilisation, in per cent."""

from benchmarks.harness import flops


def read(context: dict):
    trace = context["trace"]
    traced = [r for r in context["records"] if r.traced]
    if not trace or not traced or trace["busy_s"] <= 0:
        return None
    needed = sum(flops.unet_flops_per_image(context["family"], r.payload)
                 * int(r.payload.get("batch_size", 1)) for r in traced)
    # busy_s is the mean over the chips; together they had chips x that
    capacity = (trace["busy_s"] * context["chips"]
                * context["peak"]["bf16_flops_per_s"])
    return 100.0 * needed / capacity
