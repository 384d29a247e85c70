"""Request/response schema, sdapi-v1 compatible.

Field names and defaults follow the REST payload the reference constructs
and posts to each worker (/root/reference/scripts/distributed.py:239-265 and
worker.py:352-418): a webui client can hit this framework unchanged. Images
travel as base64 PNG strings both directions, exactly like the reference
(pil_to_64 at worker.py:45-48, decode at distributed.py:103-106).
"""

from __future__ import annotations

import base64
import io
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
from pydantic import BaseModel, Field


class GenerationPayload(BaseModel):
    """txt2img/img2img request (sdapi superset; unknown fields preserved)."""

    prompt: str = ""
    negative_prompt: str = ""
    seed: int = -1
    subseed: int = -1
    subseed_strength: float = 0.0
    steps: int = 20
    width: int = 512
    height: int = 512
    batch_size: int = 1
    n_iter: int = 1
    cfg_scale: float = 7.0
    sampler_name: str = "Euler a"
    clip_skip: int = 0  # 0 = model default; webui's setting is clip_skip-1
    # Seed-resize (webui): initial noise is drawn at THIS resolution and
    # pasted centered into the target latent, so one seed keeps its
    # composition across aspect ratios. <=0 disables.
    seed_resize_from_w: int = 0
    seed_resize_from_h: int = 0

    # img2img
    init_images: List[str] = Field(default_factory=list)  # base64 PNG
    denoising_strength: float = 0.75
    mask: Optional[str] = None          # base64 PNG, white = repaint
    inpainting_fill: int = 1            # 0 fill, 1 original (webui enum)
    mask_blur: int = 4

    # hires fix (txt2img two-pass; reference ETA models it at worker.py:205-228)
    enable_hr: bool = False
    hr_scale: float = 2.0
    hr_second_pass_steps: int = 0       # 0 = same as steps
    hr_upscaler: str = "Latent"
    hr_resize_x: int = 0
    hr_resize_y: int = 0

    # SDXL base+refiner two-model pass (webui sdapi field names)
    refiner_checkpoint: str = ""
    refiner_switch_at: float = 1.0   # fraction of steps where refiner takes over

    # per-image prompt variation: when set, image i (GLOBAL index for the
    # local backend; backends receiving a sub-range over HTTP get the
    # pre-sliced list) is conditioned on all_prompts[i]. Populated by the
    # prompt-matrix script expansion (apply_scripts) or directly by callers.
    all_prompts: Optional[List[str]] = None
    # webui script selector ("prompt matrix" is implemented natively;
    # self-looping scripts bypass distribution, scheduler/world.py)
    script_name: str = ""
    script_args: List[Any] = Field(default_factory=list)
    # every image reuses the request seed verbatim (prompt-matrix grids
    # compare prompts at a FIXED seed; webui pins all_seeds the same way)
    same_seed: bool = False
    # compiled-batch cap: engines generate in groups of this many images
    # (0 = batch_size). Script expansions set it to the user's original
    # batch_size so a 32-combination matrix doesn't become one 32-wide
    # (64 after CFG) UNet dispatch.
    group_size: int = 0
    # request-wide context length floor (in 77-token chunks) for
    # per-image prompts: conditioning must be padded to the SAME number
    # of chunks for an image regardless of which dispatch group or
    # worker slice it lands in, or the distributed gallery stops being
    # bitwise-identical to the single-host run. The planning master
    # computes it over the FULL all_prompts list and it travels with
    # every HTTP sub-range (slices can't reconstruct it).
    context_chunks: Optional[int] = None

    # fleet tier (fleet/ package): multi-tenant scheduling identity.
    # tenant keys the per-tenant quota bucket; priority_class selects the
    # scheduling class ("interactive" / "batch" / "best_effort"; empty =
    # interactive, the pre-fleet behavior for every request). slo_s, when
    # > 0, overrides the class completion SLO for THIS request (capped
    # admission still applies). All three are inert at SDTPU_FLEET=0.
    tenant: str = "default"
    priority_class: str = ""
    slo_s: float = 0.0

    # serving precision (pipeline/precision.py): "bf16" | "int8" |
    # "int8+conv"; also accepted as override_settings["precision"] (the
    # field wins). Empty = the engine policy's env default
    # (SDTPU_UNET_INT8[_CONV]) — so a request that says nothing is
    # byte-identical to pre-precision behavior. Unknown values bucket to
    # the default host-side rather than failing the request.
    precision: str = ""

    # model / misc
    override_settings: Dict[str, Any] = Field(default_factory=dict)
    styles: List[str] = Field(default_factory=list)
    # alwayson scripts payload (ControlNet etc.), keyed by script title —
    # same shape the reference packs at distributed.py:199-234.
    alwayson_scripts: Dict[str, Any] = Field(default_factory=dict)

    model_config = {"extra": "allow"}

    @property
    def total_images(self) -> int:
        return self.batch_size * self.n_iter

    def pixels_per_image(self) -> int:
        return self.width * self.height


class GenerationResult(BaseModel):
    """Mirrors webui's ``Processed``/sdapi response: images as base64 PNG,
    per-image seeds and infotexts (the reference merges these into its
    gallery at distributed.py:110-181)."""

    images: List[str] = Field(default_factory=list)   # base64 PNG
    seeds: List[int] = Field(default_factory=list)
    subseeds: List[int] = Field(default_factory=list)
    prompts: List[str] = Field(default_factory=list)
    negative_prompts: List[str] = Field(default_factory=list)
    infotexts: List[str] = Field(default_factory=list)
    parameters: Dict[str, Any] = Field(default_factory=dict)
    # which generation backend produced each image (reference appends
    # ", Worker Label: x" to infotext at distributed.py:343-349)
    worker_labels: List[str] = Field(default_factory=list)

    def extend(self, other: "GenerationResult") -> None:
        self.images.extend(other.images)
        self.seeds.extend(other.seeds)
        self.subseeds.extend(other.subseeds)
        self.prompts.extend(other.prompts)
        self.negative_prompts.extend(other.negative_prompts)
        self.infotexts.extend(other.infotexts)
        self.worker_labels.extend(other.worker_labels)


_INFOTEXT_FIELD_RE = re.compile(r'\s*([\w ]+):\s*("(?:\\.|[^"])*"|[^,]*)(?:,|$)')

#: infotext key -> payload field + parser (webui parameter-text grammar).
_INFOTEXT_KEYS = {
    "steps": ("steps", int),
    "sampler": ("sampler_name", str),
    "cfg scale": ("cfg_scale", float),
    "seed": ("seed", int),
    "variation seed": ("subseed", int),
    "variation seed strength": ("subseed_strength", float),
    "denoising strength": ("denoising_strength", float),
    "clip skip": ("clip_skip", int),
}


def parse_infotext(text: str) -> "GenerationPayload":
    """Generation-parameters text -> payload (the "send to txt2img"
    round-trip; webui's ``parse_generation_parameters``). The reference
    rewrites these strings per gallery image (distributed.py:343-349) and
    relies on webui to read them back; here the framework owns both sides,
    so ``parse_infotext(build_infotext(p, ...))`` reproduces ``p``'s core
    fields — including any ``<lora:...>`` tags kept in the prompt."""
    lines = text.split("\n")
    # only the LAST line can be the parameter list (webui grammar); prompt
    # text containing "Steps: 3 of the ritual" must survive the round trip
    params_line = ""
    if lines and re.match(r"^Steps: \d+", lines[-1].strip()):
        params_line = lines.pop()
    prompt_lines: List[str] = []
    neg_lines: List[str] = []
    in_negative = False
    for line in lines:
        if not in_negative and line.startswith("Negative prompt:"):
            in_negative = True
            neg_lines.append(line[len("Negative prompt:"):].strip())
        elif in_negative:
            # multi-line negative prompts continue until the params line
            neg_lines.append(line)
        else:
            prompt_lines.append(line)
    payload = GenerationPayload(
        prompt="\n".join(prompt_lines).strip(),
        negative_prompt="\n".join(neg_lines).strip())
    for m in _INFOTEXT_FIELD_RE.finditer(params_line):
        key = m.group(1).strip().lower()
        value = m.group(2).strip().strip('"')
        if key == "size" and "x" in value:
            w, _, h = value.partition("x")
            try:
                payload.width, payload.height = int(w), int(h)
            except ValueError:
                pass
            continue
        if key == "seed resize from" and "x" in value:
            w, _, h = value.partition("x")
            try:
                payload.seed_resize_from_w = int(w)
                payload.seed_resize_from_h = int(h)
            except ValueError:
                pass
            continue
        if key == "ensd":
            try:
                payload.override_settings["eta_noise_seed_delta"] = \
                    int(value)
            except ValueError:
                pass
            continue
        target = _INFOTEXT_KEYS.get(key)
        if target is None:
            continue
        field, conv = target
        try:
            setattr(payload, field, conv(value))
        except ValueError:
            pass
    return payload


def expand_prompt_matrix(prompt: str) -> List[str]:
    """webui prompt-matrix grammar: ``base|opt1|opt2`` -> one prompt per
    subset of the options, in binary-counter order (webui
    scripts/prompt_matrix.py semantics): index i includes option j iff bit
    j of i is set. 2^(n_options) prompts total."""
    parts = [p.strip() for p in prompt.split("|")]
    base, options = parts[0], parts[1:]
    if len(options) > 10:
        # 2^n combinations: unbounded '|' counts would OOM the node while
        # it holds the generation lock (10 options = 1024 images already)
        raise ValueError(
            f"prompt matrix with {len(options)} options would generate "
            f"2^{len(options)} images; the limit is 10 options (1024)")
    out = []
    for i in range(1 << len(options)):
        chosen = [options[j] for j in range(len(options)) if i & (1 << j)]
        out.append(", ".join([base] + chosen) if chosen else base)
    return out


def apply_scripts(payload: "GenerationPayload") -> "GenerationPayload":
    """Expand native script semantics into the payload. Idempotent — safe
    to call at every entry point (World.execute, ApiServer, CLI).

    ``prompt matrix``: the prompt's ``|`` alternatives expand into
    ``all_prompts`` (one image per combination, fixed seed), replacing
    batch_size/n_iter — the webui script this reproduces runs server-side
    on every node of the reference's fleet.

    ``prompts from file or textbox``: one image per non-empty line of the
    script's text argument (webui's built-in; lines starting with ``#``
    are comments), normal per-image seed progression.
    """
    if payload.all_prompts:
        return payload  # already expanded
    script = payload.script_name.strip().lower()
    if script == "prompt matrix" and "|" in payload.prompt:
        payload = payload.model_copy()
        payload.all_prompts = expand_prompt_matrix(payload.prompt)
        # the user's batch_size becomes the per-dispatch group cap; the
        # matrix size becomes the request total
        payload.group_size = max(1, payload.batch_size)
        payload.batch_size = len(payload.all_prompts)
        payload.n_iter = 1
        payload.same_seed = True
    elif script == "prompts from file or textbox":
        # webui run() signature: (checkbox_iterate, checkbox_iterate_batches,
        # prompt_txt) — the text rides last in script_args. With
        # checkbox_iterate OFF (the default) every line runs at the SAME
        # seed; ON advances the seed per line (webui semantics).
        args = payload.script_args or []
        text = next((a for a in reversed(args)
                     if isinstance(a, str) and a.strip()), "")
        iterate = bool(next((a for a in args if isinstance(a, bool)), False))
        lines = [ln.strip() for ln in text.splitlines()]
        lines = [ln for ln in lines if ln and not ln.startswith("#")]
        if lines:
            payload = payload.model_copy()
            payload.all_prompts = lines
            payload.group_size = max(1, payload.batch_size)
            payload.batch_size = len(lines)
            payload.n_iter = 1
            payload.same_seed = not iterate
    return payload


#: title of the always-on script that asks for prompt expansion
PROMPT_EXPANSION = "prompt expansion"


class PromptExpansion(BaseModel):
    """Arguments of the always-on ``prompt expansion`` script
    (``alwayson_scripts: {"prompt expansion": {"args": [{...}]}}``): the
    worker's resident language model (``ModelFamily.expander``) continues
    ``instruction`` + the user's prompt, and the continuation is appended
    to the prompt before CLIP sees it (upstream: Dynamic Prompts' Magic
    Prompt, the promptgen extension). Every setting is an argument."""

    #: the operator's text before every prompt; its cache is kept
    instruction: str = ""
    max_new_tokens: int = 64
    #: 0 = greedy; draws are keyed by the image's seed
    temperature: float = 1.0
    #: keep decoding past an end-of-sequence token
    ignore_eos: bool = False
    #: pin the conditioning to this many 77-token chunks: the LAST
    #: 75 * context_chunks CLIP tokens of prompt + expansion go on, so one
    #: UNet shape serves every request (None: as long as it comes out)
    context_chunks: Optional[int] = None


def prompt_expansion_args(payload: "GenerationPayload"
                          ) -> Optional[PromptExpansion]:
    """The request's prompt-expansion arguments, or None when it has no
    such script (or the script asks for no token)."""
    for title, script in (payload.alwayson_scripts or {}).items():
        if title.strip().lower() != PROMPT_EXPANSION:
            continue
        args = (script or {}).get("args") if isinstance(script, dict) \
            else None
        first = args[0] if args else {}
        parsed = PromptExpansion(**(first if isinstance(first, dict) else {}))
        return parsed if parsed.max_new_tokens > 0 else None
    return None


def fix_seed(seed: Optional[int]) -> int:
    """-1 -> fresh random seed (webui fix_seed semantics; the reference
    records the fixed value before fan-out so every worker agrees on the
    seed base, distributed.py:252-254)."""
    if seed is None or int(seed) == -1:
        import secrets

        return secrets.randbelow(2**32)
    return int(seed) % 2**32


def canonical_dump(payload: "GenerationPayload") -> Dict[str, Any]:
    """The payload as a fingerprint-stable dict (cache/keys.py hashes it).

    Two requests that generate the same bytes must canonicalize to the
    same dict regardless of how they were spelled: the pydantic dump
    materializes every declared field (so omitted defaults equal
    spelled-out ones) in declaration order (so construction order never
    matters), and ``extra="allow"`` passthrough fields ride along — an
    unknown field MIGHT change behavior downstream, so it must change
    the fingerprint. Callers hash this only AFTER ``fix_seed`` and
    ``apply_scripts``, when the payload describes the exact work.
    """
    return payload.model_dump()


# --------------------------------------------------------------------------
# image <-> base64 PNG (wire format parity with the reference)
# --------------------------------------------------------------------------

class Base64Text(str):
    """Text of the base64 alphabet alone, as :func:`encode_b64png` made
    it: a JSON writer may copy it between quotes without reading it
    (``server/api.py:json_body``). Any other ``str`` is read as before."""

    __slots__ = ()


def encode_b64png(img: np.ndarray) -> Tuple[str, int]:
    """(H,W,3) uint8 -> (base64 PNG string, strips it was deflated as).

    Uses the native C++ encoder (runtime/native.py) when available — PNG
    encoding is the host-side cost of the wire format after the TPU has
    finished, so that encoder spreads an image's scanlines over the host's
    idle cores as strips and says how many — and falls back to PIL (one
    strip) otherwise."""
    from stable_diffusion_webui_distributed_tpu.runtime import native

    encoded = native.encode_png(np.asarray(img))
    if encoded is None:
        from PIL import Image

        buf = io.BytesIO()
        Image.fromarray(img).save(buf, format="PNG")
        encoded = buf.getvalue(), 1
    data, strips = encoded
    return Base64Text(base64.b64encode(data).decode("ascii")), strips


def array_to_b64png(img: np.ndarray) -> str:
    """(H,W,3) uint8 -> base64 PNG string (:func:`encode_b64png`'s)."""
    return encode_b64png(img)[0]


def b64png_to_array(data: str) -> np.ndarray:
    """base64 PNG (optionally data-URL prefixed) -> (H,W,3) uint8."""
    from PIL import Image

    if "," in data and data.strip().startswith("data:"):
        data = data.split(",", 1)[1]
    img = Image.open(io.BytesIO(base64.b64decode(data)))
    return np.asarray(img.convert("RGB"))


def build_infotext(payload: GenerationPayload, seed: int, subseed: int,
                   model_name: str = "", width: int = 0, height: int = 0,
                   extra: str = "", prompt_override: Optional[str] = None
                   ) -> str:
    """webui-format generation parameters text (the string the reference
    rewrites per gallery image at distributed.py:343-349).
    ``prompt_override``: this image's own prompt (per-image variation)."""
    lines = [payload.prompt if prompt_override is None else prompt_override]
    if payload.negative_prompt:
        lines.append(f"Negative prompt: {payload.negative_prompt}")
    fields = [
        f"Steps: {payload.steps}",
        f"Sampler: {payload.sampler_name}",
        f"CFG scale: {payload.cfg_scale}",
        f"Seed: {seed}",
        f"Size: {width or payload.width}x{height or payload.height}",
    ]
    if model_name:
        fields.append(f"Model: {model_name}")
    if payload.subseed_strength > 0:
        fields.append(f"Variation seed: {subseed}")
        fields.append(f"Variation seed strength: {payload.subseed_strength}")
    if payload.seed_resize_from_w > 0 and payload.seed_resize_from_h > 0:
        fields.append(f"Seed resize from: "
                      f"{payload.seed_resize_from_w}x"
                      f"{payload.seed_resize_from_h}")
    ensd = (payload.override_settings or {}).get("eta_noise_seed_delta", 0)
    if ensd:
        fields.append(f"ENSD: {ensd}")
    if payload.denoising_strength != 0.75 and (
        payload.init_images or payload.enable_hr
    ):
        fields.append(f"Denoising strength: {payload.denoising_strength}")
    if extra:
        fields.append(extra)
    lines.append(", ".join(fields))
    return "\n".join(lines)
