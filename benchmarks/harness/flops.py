"""Operations the UNet needs, from shapes alone: 2 x multiply-accumulates of
every convolution (taps on the zero padding left out), linear layer and
attention matmul of one forward pass of
``models/unet.py``'s architecture. Norms, activations, softmax and residual
adds are left out (a few per cent; benchmarks/tests compares with XLA's cost
analysis at the tiny width and states the margin).

A request needs ``steps`` forward passes (img2img: webui's
``int(min(denoising_strength, 0.999) * steps)``) at batch 2 per image (conditional
and unconditional rows of classifier-free guidance), for the samplers that
evaluate the model once a step (Euler a, Euler, DDIM, ...).
"""

from __future__ import annotations

#: model evaluations per step; a sampler missing here is an error
EVALS_PER_STEP = {"Euler a": 1, "Euler": 1, "DDIM": 1, "LMS": 1,
                  "DPM++ 2M": 1, "DPM++ 2M Karras": 1, "Heun": 2,
                  "DPM2": 2, "DPM2 a": 2}


def _taps(n: int, k: int, stride: int) -> int:
    """Kernel taps that land inside an edge of ``n`` inputs, summed over
    the outputs of a ``k``-wide window at padding ``k // 2``: a tap on the
    zero padding is not work the algorithm needs (XLA leaves it out too)."""
    pad = k // 2
    out = (n + 2 * pad - k) // stride + 1
    return sum(1 for o in range(out) for t in range(k)
               if 0 <= o * stride - pad + t < n)


def _conv(h, w, cin, cout, k=3, stride=1):
    """``h`` x ``w`` is the INPUT's size."""
    return 2 * _taps(h, k, stride) * _taps(w, k, stride) * cin * cout


def _linear(tokens, cin, cout):
    return 2 * tokens * cin * cout


def _resblock(h, w, cin, cout, time_dim):
    total = _conv(h, w, cin, cout) + _conv(h, w, cout, cout)
    total += _linear(1, time_dim, cout)
    if cin != cout:
        total += _conv(h, w, cin, cout, k=1)
    return total


def _transformer(h, w, c, depth, ctx_len, ctx_dim):
    t = h * w
    block = (
        _linear(t, c, 3 * c)            # self-attention qkv
        + 2 * 2 * t * t * c             # q.k^T and p.v over all heads
        + _linear(t, c, c)              # out_proj
        + _linear(t, c, c)              # cross-attention q
        + _linear(ctx_len, ctx_dim, 2 * c)
        + 2 * 2 * t * ctx_len * c
        + _linear(t, c, c)
        + _linear(t, c, 8 * c)          # GEGLU proj
        + _linear(t, 4 * c, c))         # ff_out
    return 2 * _linear(t, c, c) + depth * block     # proj_in, proj_out


def unet_forward_flops(cfg, h: int, w: int, ctx_len: int = 77) -> int:
    """One forward pass of one batch row at an ``h`` x ``w`` latent."""
    chans = cfg.block_out_channels
    ch0 = chans[0]
    time_dim = 4 * ch0
    total = _linear(1, ch0, time_dim) + _linear(1, time_dim, time_dim)
    if cfg.addition_embed_dim:
        total += (_linear(1, cfg.projection_input_dim, time_dim)
                  + _linear(1, time_dim, time_dim))
    total += _conv(h, w, cfg.in_channels, ch0)
    skips = [(ch0, h, w)]
    x = ch0
    for level, (ch, depth) in enumerate(zip(chans, cfg.down_blocks)):
        for _ in range(cfg.layers_per_block):
            total += _resblock(h, w, x, ch, time_dim)
            x = ch
            if depth is not None:
                total += _transformer(h, w, ch, depth, ctx_len,
                                      cfg.cross_attention_dim)
            skips.append((x, h, w))
        if level < len(chans) - 1:
            total += _conv(h, w, ch, ch, stride=2)
            h, w = (h + 1) // 2, (w + 1) // 2
            skips.append((x, h, w))
    mid = chans[-1]
    total += 2 * _resblock(h, w, mid, mid, time_dim)
    if cfg.mid_block_depth is not None:
        total += _transformer(h, w, mid, cfg.mid_block_depth, ctx_len,
                              cfg.cross_attention_dim)
    for level in reversed(range(len(chans))):
        ch, depth = chans[level], cfg.down_blocks[level]
        for _ in range(cfg.layers_per_block + 1):
            skip_ch, h, w = skips.pop()
            total += _resblock(h, w, x + skip_ch, ch, time_dim)
            x = ch
            if depth is not None:
                total += _transformer(h, w, ch, depth, ctx_len,
                                      cfg.cross_attention_dim)
        if level > 0:
            h, w = 2 * h, 2 * w
            total += _conv(h, w, ch, ch)
    assert not skips
    return total + _conv(h, w, ch0, cfg.out_channels)


def unet_flops_per_image(family, payload: dict) -> int:
    """UNet operations one image of ``payload`` needs."""
    scale = family.vae_scale_factor
    h, w = payload["height"] // scale, payload["width"] // scale
    evals = EVALS_PER_STEP[payload.get("sampler_name", "Euler a")]
    steps = int(payload["steps"])
    if payload.get("init_images"):      # img2img runs the last t_enc steps
        steps = int(min(payload.get("denoising_strength", 0.75), 0.999)
                    * steps)
    return 2 * evals * steps * unet_forward_flops(family.unet, h, w)
