"""The components of a UNet + CLIP + VAE family whose resident language
model (``ModelFamily.expander``, models/lm.py) decodes several sequences a
step: what ``components/unet_clip_vae_lm.py`` gives, with one rule more, for
the language model's token table.

``harness/weights.py`` draws a table at variance 1/features (rows of norm
1), while every sublayer reads a normed input and so adds an output of norm
about sqrt(features) to the residual stream. A token's own row is then a
fiftieth of what the first attention adds, and attention over a long
context adds nearly the SAME vector (a mean of a thousand values) to every
position: layer by layer every token's router sees more of one shared
vector and chooses the same experts. A step of ONE sequence reads its 8
experts whatever they are, so the siblings' cells do not see this; a step
of several is measured by how many DISTINCT experts it streams, and a
collapsed router takes the mechanism out of the cell (a checkpoint's
routers are trained to balance; even routing gives four tokens
64 (1 - 0.875^4) = 26.5 distinct experts of their 32 picks). So the table
is drawn at variance 1: rows of norm sqrt(features), the scale of the
sublayers' outputs.

Both rules at the published widths on the CPU (8 layers, float32 compute,
1 280 seeded positions, seed 45; PR 45, review round; PERF.md section 6 has
the script's name): the share of a layer's picks that its 8 busiest
experts take (even: 12.5 %), and the distinct experts four late tokens
choose:

    layer                    0     1     2     3     4     5     6     7
    1/features   busiest   30.3  39.6  55.5  72.1  73.6  65.6  76.5  73.5 %
                 distinct  22.3  21.6  15.1  12.5  11.2  12.8  12.4  11.3
    1 (this)     busiest   14.6  15.7  17.5  21.6  28.3  29.0  33.4  40.1 %
                 distinct  26.5  26.6  26.3  26.1  24.9  24.5  23.4  22.0

On the chip a decode step of four sequences read 12.29 distinct experts a
layer under the first rule and 24.29 under this one (mean of the rows
above: 14.9 and 25.0; my chip runs, PR 45).
"""

import functools
import importlib.util
import math
import os

TABLE = "embed_tokens/embedding"


@functools.lru_cache(maxsize=None)
def _base():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "unet_clip_vae_lm.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_components_unet_clip_vae_lm", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def component_inits(family):
    return _base().component_inits(family)


def leaf_rule(path: str, shape):
    if path == TABLE:
        return "draw", math.sqrt(3.0)       # uniform, variance 1
    return _base().leaf_rule(path, shape)
