"""MoonViT, Kimi-VL-A3B's vision tower, on the packed CIM linear deploy
path: the system under test.

The weights and calibrated scales are the reference's
(``kimi-vl-a3b-moonvit.reference.py``); they are handed to the program in
its own layout (``repro.models.vit``), every CIM linear is packed by
``repro.api.model_artifact`` into int4 nibble digit planes, and the unit
of work is one jitted deploy ``vit.forward`` over a pack of seeded images
at native resolution, the projector tokens of every image copied back to
the host. The forward also hands out each block's output (its taps),
which stay on the device for the window's last unit: the check gives the
reference's every stage (patch embedding, each block, projector) the
program's own input to that stage and compares the stage's output, so a
rounding difference in one block is not carried through the next.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import loader, roofline, traffic

REF = loader.sibling(__file__, "kimi-vl-a3b-moonvit.reference.py")
#: the traffic stream of this cell's pixels (``traffic.py`` draws on
#: streams 1, 4 and 5)
PIXELS = 6


def shapes(mix):
    """(height, width) of every image of a pack, in pack order."""
    return [(im["height"], im["width"]) for im in mix["images"]
            for _ in range(im["count"])]


def pixels(seed, pack, i, hw):
    """Image ``i`` of pack ``pack`` (-1: the calibration image): uniform
    uint8 pixels, normalized, float32 (H, W, 3)."""
    g = traffic.rng(seed, PIXELS * 1_000_003 + 64 * pack + i)
    return traffic.normalize_pixels(
        g.integers(0, 256, size=tuple(hw) + (3,), dtype=np.uint8))


def tokens(sz, mix):
    """(patch tokens, merged tokens) of every image of a pack."""
    vz = REF.vision(sz)
    n = [(h // vz["patch"]) * (w // vz["patch"]) for h, w in shapes(mix)]
    return n, [k // vz["merge"] ** 2 for k in n]


def unit_calls(sz, mix):
    """Every CIM linear of one forward: a block's four at the pack's patch
    tokens, once per layer, and the projector's two at its merged
    tokens."""
    vz, rows = REF.vision(sz), sz["cim"]["array_rows"]
    n, merged = tokens(sz, mix)
    lin = REF.linear_shapes(sz)

    def call(m, name, count=1):
        k, cols = lin[name]
        return roofline.CimCall(m=m, k=k, n=cols, act_elems=m * k,
                                k_tiles=-(-k // rows), out_bytes=4,
                                count=count)
    return ([call(sum(n), name, vz["layers"])
             for name, _, _ in REF.BLOCK_LINEARS]
            + [call(sum(merged), name) for name in ("proj1", "proj2")])


def unit_flops(sz, mix):
    """Model FLOPs of one forward: the CIM linears, attention within each
    image (QK and AV, 4 n^2 d a layer), and the patch embedding."""
    vz = REF.vision(sz)
    n, _ = tokens(sz, mix)
    flops = sum(2.0 * c.m * c.k * c.n * c.count for c in unit_calls(sz, mix))
    flops += 4.0 * sum(k * k for k in n) * vz["d"] * vz["layers"]
    flops += 2.0 * sum(n) * 3 * vz["patch"] ** 2 * vz["d"]
    return flops


def unit_work(sz, mix):
    return {"img_s": len(shapes(mix))}


def cim_config(sz):
    from repro.core import CIMConfig
    c = sz["cim"]
    return CIMConfig(enabled=True, mode="emulate",
                     weight_bits=c["weight_bits"], cell_bits=c["cell_bits"],
                     act_bits=c["act_bits"], psum_bits=c["psum_bits"],
                     array_rows=c["array_rows"], array_cols=c["array_cols"],
                     pack_dtype=c["pack_dtype"], use_kernel=True)


def model_config(sz):
    """The program's config of the tower (``repro.configs``) at the sizes
    ``sz`` states, on the emulate backend that the pack starts from."""
    from repro.configs import kimi_vl_a3b_moonvit
    from repro.configs.base import VisionConfig
    vz = REF.vision(sz)
    return kimi_vl_a3b_moonvit.config().replace(
        name=sz["name"], n_layers=vz["layers"], d_model=vz["d"],
        n_heads=vz["heads"], n_kv_heads=vz["heads"], head_dim=vz["hd"],
        d_ff=vz["ff"], rope_theta=vz["theta"], patch_size=vz["patch"],
        vision=VisionConfig(pos_grid=vz["pos"], merge=vz["merge"],
                            out_dim=vz["out"]),
        compute_dtype=sz["compute_dtype"], cim=cim_config(sz))


def _cim_node(lw, ls, rows):
    """A CIM linear in the program's layout: w = code * s_w of its
    (array tile, column), exactly on the weight grid."""
    k = lw["code"].shape[-2]
    s = jnp.repeat(lw["s_w"], rows, axis=-2)[..., :k, :]
    return {"w": lw["code"].astype(jnp.float32) * s, "s_w": lw["s_w"],
            "s_p": ls["s_p"], "s_a": ls["s_a"]}


def program_params(sz, weights, scales):
    """The reference's weights and scales as ``vit.specs`` lays them out;
    LayerNorm weights 1."""
    vz, rows = REF.vision(sz), sz["cim"]["array_rows"]

    def ln(*lead):
        return {"scale": jnp.ones(lead + (vz["d"],), jnp.float32)}
    block = {n: _cim_node(weights["layers"][n], scales["layers"][n], rows)
             for n, _, _ in REF.BLOCK_LINEARS}
    return {
        "patch": {"w": weights["patch_w"], "b": weights["patch_b"]},
        "pos": weights["pos"],
        "layers": {"ln1": ln(vz["layers"]), "ln2": ln(vz["layers"]),
                   "wqkv": block["wqkv"], "wo": block["wo"],
                   "mlp": {"wu": block["wu"], "wd": block["wd"]}},
        "ln_f": ln(), "proj_ln": ln(),
        "proj1": _cim_node(weights["proj1"], scales["proj1"], rows),
        "proj2": _cim_node(weights["proj2"], scales["proj2"], rows),
    }


def _same_structure(params, cfg):
    from repro.models import vit
    from repro.nn.module import init_params
    want = jax.eval_shape(lambda k: init_params(vit.specs(cfg), k),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the program's vit parameter tree changed: "
                           f"{jax.tree.structure(want)}")


def jitted_forward(cfg):
    """The unit's program: a pack of images -> (every image's projector
    tokens, concatenated in pack order; the pack's taps)."""
    from repro.models import vit

    def forward(p, images):
        out, taps = vit.forward(p, images, cfg, return_taps=True)
        return jnp.concatenate(out), taps
    return jax.jit(forward)


class Cell:
    def __init__(self, sz, mix, seed, log):
        from repro.api import model_artifact
        self.sz, self.mix, self.seed = sz, mix, seed
        self.shapes = shapes(mix)
        cfg = model_config(sz)
        with jax.profiler.TraceAnnotation("perfbench.build"):
            weights = REF.make_weights(sz, traffic.jax_key(seed,
                                                           traffic.WEIGHTS))
            cal = pixels(seed, -1, 0, sz["calibration"]["image"])
            self.scales = REF.calibrate(sz, weights, jnp.asarray(cal))
            params = jax.jit(lambda w, s: program_params(sz, w, s))(
                weights, self.scales)
            del weights
            _same_structure(params, cfg)
            art = model_artifact(params, cfg.cim, meta={"arch": sz["name"]})
            jax.block_until_ready(art.params)
            del params
        self.params = art.params
        if not (art.config.mode == "deploy" and art.config.use_kernel):
            raise RuntimeError(f"artifact serves on {art.config}")
        self.forward = jitted_forward(cfg.replace(cim=art.config))
        with jax.profiler.TraceAnnotation("perfbench.images"):
            self.packs = [[jax.device_put(pixels(seed, j, i, hw))
                           for i, hw in enumerate(self.shapes)]
                          for j in range(mix["distinct_packs"])]
            jax.block_until_ready(self.packs)
        n, merged = tokens(sz, mix)
        self.patch_at = np.cumsum([0] + n)
        self.offsets = np.cumsum([0] + merged)
        t0 = time.perf_counter()
        np.asarray(self.forward(self.params, self.packs[0])[0])
        log(f"warm-up forward {time.perf_counter() - t0:.2f}s")
        self.last = None     # (pack index, tokens, taps) of the last unit
        self.attempted = 0
        self.failed = 0

    def unit(self, i):
        j = i % len(self.packs)
        self.attempted += len(self.shapes)
        self.last = None                  # frees the last unit's taps
        with jax.profiler.TraceAnnotation("perfbench.forward"):
            y, taps = self.forward(self.params, self.packs[j])
            y = np.asarray(y)
        if y.shape != (self.offsets[-1], REF.vision(self.sz)["out"]):
            self.failed += len(self.shapes)
        else:
            self.failed += sum(not np.isfinite(self._image(y, k)).all()
                               for k in range(len(self.shapes)))
        self.last = (j, y, taps)
        return unit_work(self.sz, self.mix)

    def _image(self, y, k):
        return y[self.offsets[k]:self.offsets[k + 1]]

    def release(self):
        del self.params, self.packs, self.forward

    def check(self, limits, controls=()):
        """Every stage of the window's last unit against the reference
        given the program's own input to that stage (the unit's taps), so
        a code that one stage flips by rounding is not carried into the
        next. For each image the stages are the patch embedding on its
        pixels; per block the attention core (LayerNorm, fused q, k, v
        linear, RoPE, attention) on the block's input, the output linear
        and residual on the program's attention output, and the MLP half
        on the program's residual after the attention half; then the
        projector on the last block's output. Per (stage, image) the
        error is ||y - ref|| over ||ref|| where a stage makes a new
        tensor, and over ||ref - x|| where it adds to its residual input
        x. Their mean and their worst, the worst stage and the worst of
        each kind of stage, under "program" (and the same under each
        control precision, with the reference in that precision in the
        program's place)."""
        sz, vz = self.sz, REF.vision(self.sz)
        j, y_all, taps = self.last
        blocks = taps["blocks"]
        weights = REF.make_weights(sz, traffic.jax_key(self.seed,
                                                       traffic.WEIGHTS))
        scales = self.scales
        errs = {"program": [], **{c: [] for c in controls}}

        def compare(stage, outs, ref, base):
            """outs: {"program" or a control: its output of the stage}."""
            scale = float(jnp.linalg.norm(ref if base is None
                                          else ref - base))
            for c, y in outs.items():
                y = jnp.asarray(y)
                err = (float(jnp.linalg.norm(y - ref)) / scale
                       if y.shape == ref.shape else np.inf)
                errs[c].append((err if np.isfinite(err) else np.inf, stage))

        for k, hw in enumerate(self.shapes):
            img = jnp.asarray(pixels(self.seed, j, k, hw))
            a, b = self.patch_at[k], self.patch_at[k + 1]
            x = taps["embed"][a:b]
            compare("embed", {"program": x, **{
                c: REF.embed(sz, weights, img, c) for c in controls}},
                REF.embed(sz, weights, img), None)
            for i in range(vz["layers"]):
                prog = [blocks[t][i, a:b] for t in ("attn", "mid", "out")]
                ref, *ctl = (REF.block_stages(sz, weights, scales, i, x,
                                              prog[0], prog[1], hw, c)
                             for c in (None,) + tuple(controls))
                for s, (name, base) in enumerate(
                        (("attn", None), ("attn_out", x),
                         ("mlp", prog[1]))):
                    compare(f"{name}{i}", {"program": prog[s], **{
                        c: o[s] for c, o in zip(controls, ctl)}},
                        ref[s], base)
                x = prog[2]
            compare("projector", {"program": self._image(y_all, k), **{
                c: REF.project(sz, weights, scales, x, hw, c)
                for c in controls}},
                REF.project(sz, weights, scales, x, hw), None)
        del weights, taps, blocks
        out = {"images": len(self.shapes), "pack": int(j)}
        for c, e in errs.items():
            v = np.asarray([err for err, _ in e])
            kinds = {}
            for err, stage in e:
                kind = stage.rstrip("0123456789")
                kinds[kind] = max(kinds.get(kind, 0.0), err)
            out[c] = {"stage_err_mean": float(np.mean(v)),
                      "stage_err_max": float(np.max(v)),
                      "worst_stage": e[int(np.argmax(v))][1],
                      "worst_by_kind": kinds}
        return out


def build(sz, mix, seed, log):
    if mix["unit"] != "encode":
        raise ValueError(f"{sz['name']} runs the 'encode' unit, not "
                         f"{mix['unit']!r}")
    return Cell(sz, mix, seed, log)
