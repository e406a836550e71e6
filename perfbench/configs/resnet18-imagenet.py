"""ResNet-18 on the packed CIM conv deploy path: the system under test.

The weights, scales and BN statistics are the reference's
(``resnet18-imagenet.reference.py``); they are handed to the program in
its own layout (``repro.models.resnet``), every CIM conv is packed by
``repro.api.model_artifact`` into int4 nibble digit planes, and the unit
of work is one jitted deploy ``resnet.forward`` over a batch of seeded
images, its logits copied back to the host.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import loader, roofline, traffic

REF = loader.sibling(__file__, "resnet18-imagenet.reference.py")


def unit_calls(sz, mix):
    """Every CIM conv of one forward at the batch and input size."""
    cim, b, h = sz["cim"], mix["batch"], mix["hw"]
    out = []
    for _, c_in, c_out, stride, proj in REF.blocks(sz):
        h_out = -(-h // stride)
        out.append(roofline.conv_call(b, h, h, c_in, c_out, 3, 3, stride,
                                      cim, out_bytes=4))
        out.append(roofline.conv_call(b, h_out, h_out, c_out, c_out, 3, 3,
                                      1, cim, out_bytes=4))
        if proj:
            out.append(roofline.conv_call(b, h, h, c_in, c_out, 1, 1,
                                          stride, cim, out_bytes=4))
        h = h_out
    return out


def unit_flops(sz, mix):
    """Model FLOPs of one forward: every conv (stem included) at its
    output size, and the classifier."""
    b, h = mix["batch"], mix["hw"]
    flops = 2 * h * h * 9 * 3 * sz["widths"][0]
    for c in unit_calls(sz, mix):
        flops += 2 * (c.m // b) * c.k * c.n
    flops += 2 * sz["widths"][-1] * sz["n_classes"]
    return float(b * flops)


def unit_work(sz, mix):
    return {"img_s": mix["batch"]}


def cim_config(sz):
    from repro.core import CIMConfig
    c = sz["cim"]
    return CIMConfig(enabled=True, mode="emulate",
                     weight_bits=c["weight_bits"], cell_bits=c["cell_bits"],
                     act_bits=c["act_bits"], psum_bits=c["psum_bits"],
                     array_rows=c["array_rows"], array_cols=c["array_cols"],
                     pack_dtype=c["pack_dtype"], use_kernel=True)


def resnet_config(sz, cim):
    from repro.models import resnet
    return resnet.ResNetConfig(name=sz["name"], depth=sz["depth"],
                               n_classes=sz["n_classes"],
                               widths=tuple(sz["widths"]), in_hw=sz["in_hw"],
                               cim=cim)


def _program_trees(sz, weights, scales, bn):
    """(params, state) in the program's layout: conv w = code * s_w of
    its (array tile, output channel), exactly on the weight grid."""
    cim = sz["cim"]
    ones = lambda c: {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}
    w0 = sz["widths"][0]
    params = {"stem": {"w": weights["stem"]}, "stem_bn": ones(w0),
              "fc": {"w": weights["fc"],
                     "b": jnp.zeros((sz["n_classes"],))}}
    state = {"stem_bn": bn["stem_bn"]}
    for blk, conv, kh, c_in, c_out, _ in REF.convs(sz):
        lw, ls = weights[f"{blk}.{conv}"], scales[f"{blk}.{conv}"]
        cpa, _ = REF.tiling(kh, c_in, cim)
        s_full = lw["s_w"][jnp.arange(c_in) // cpa]          # (c_in, c_out)
        bn_name = {"conv1": "bn1", "conv2": "bn2", "proj": "bn_p"}[conv]
        params.setdefault(blk, {})[conv] = {
            "w": lw["code"].astype(jnp.float32) * s_full[None, None],
            "s_w": lw["s_w"], "s_p": ls["s_p"], "s_a": ls["s_a"]}
        params[blk][bn_name] = ones(c_out)
        state.setdefault(blk, {})[bn_name] = bn[f"{blk}.{bn_name}"]
    return params, state


def _same_structure(trees, rcfg):
    from repro.models import resnet
    want = jax.eval_shape(lambda k: resnet.init(k, rcfg),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       trees)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the program's ResNet parameter tree changed: "
                           f"{jax.tree.structure(want)}")


def images(seed, i, batch, hw):
    return traffic.normalize_pixels(traffic.pixels(seed, i, batch, hw))


def weights_and_scales(sz, seed):
    weights = REF.make_weights(sz, traffic.jax_key(seed, traffic.WEIGHTS))
    cal = sz["calibration"]
    x = images(seed, -1, cal["images"], sz["in_hw"])
    scales, bn = REF.calibrate(sz, weights, jnp.asarray(x))
    return weights, scales, bn


class Cell:
    def __init__(self, sz, mix, seed, log):
        from repro.api import model_artifact
        from repro.models import resnet
        if mix["hw"] != sz["in_hw"]:
            raise ValueError(f"images of {mix['hw']}, config {sz['in_hw']}")
        self.sz, self.mix, self.seed = sz, mix, seed
        rcfg = resnet_config(sz, cim_config(sz))
        with jax.profiler.TraceAnnotation("perfbench.build"):
            weights, self.scales, self.bn = weights_and_scales(sz, seed)
            params, self.state = jax.jit(
                lambda w, s, b: _program_trees(sz, w, s, b))(
                    weights, self.scales, self.bn)
            del weights
            _same_structure((params, self.state), rcfg)
            art = model_artifact(params, rcfg.cim, meta={"arch": sz["name"]})
            jax.block_until_ready(art.params)
            del params
        self.params = art.params
        serve_cfg = dataclasses.replace(rcfg, cim=art.config)
        if not (art.config.mode == "deploy" and art.config.use_kernel):
            raise RuntimeError(f"artifact serves on {art.config}")
        self.forward = jax.jit(lambda p, s, x: resnet.forward(
            p, s, x, serve_cfg, train=False)[0])
        with jax.profiler.TraceAnnotation("perfbench.images"):
            self.images_dev = [
                jax.device_put(images(seed, i, mix["batch"], mix["hw"]))
                for i in range(mix["distinct_batches"])]
            jax.block_until_ready(self.images_dev)
        t0 = time.perf_counter()
        np.asarray(self.forward(self.params, self.state, self.images_dev[0]))
        log(f"warm-up forward {time.perf_counter() - t0:.2f}s")
        self.outputs = []                 # (batch index, logits) per unit
        self.attempted = 0
        self.failed = 0

    def unit(self, i):
        j = i % len(self.images_dev)
        b = self.mix["batch"]
        self.attempted += b
        with jax.profiler.TraceAnnotation("perfbench.forward"):
            y = np.asarray(self.forward(self.params, self.state,
                                        self.images_dev[j]))
        if y.shape != (b, self.sz["n_classes"]) or not np.isfinite(y).all():
            self.failed += b
        self.outputs.append((j, y))
        return unit_work(self.sz, self.mix)

    def release(self):
        del self.params, self.images_dev, self.forward

    def check(self, limits, controls=()):
        """Reference over a seeded sample of the images the window
        classified: per image max|logit - reference| / max|reference|,
        their mean and their worst, under "program" (and under each
        control precision the same with that control in its place)."""
        b, rows = self.mix["batch"], self.sz["reference_images"]
        pick = traffic.sample(self.seed, len(self.outputs) * b,
                              limits["sample_images"])
        weights = REF.make_weights(self.sz, traffic.jax_key(
            self.seed, traffic.WEIGHTS))
        errs = {"program": [], **{c: [] for c in controls}}
        for lo in range(0, len(pick), rows):
            chunk = pick[lo:lo + rows]
            x = np.stack([images(self.seed, self.outputs[r // b][0], b,
                                 self.mix["hw"])[r % b] for r in chunk])
            x = jnp.asarray(np.pad(x, ((0, rows - len(chunk)),)
                                   + ((0, 0),) * 3))
            ref = np.asarray(REF.logits(self.sz, weights, self.scales,
                                        self.bn, x))[:len(chunk)]
            for k in errs:
                y = (np.stack([self.outputs[r // b][1][r % b]
                               for r in chunk]) if k == "program" else
                     np.asarray(REF.logits(self.sz, weights, self.scales,
                                           self.bn, x, k))[:len(chunk)])
                errs[k].append(np.max(np.abs(y - ref), axis=-1)
                               / np.max(np.abs(ref), axis=-1))
        del weights
        out = {"images": len(pick)}
        for k, e in errs.items():
            e = np.concatenate(e)
            out[k] = {"logit_rel_err_mean": float(np.mean(e)),
                      "logit_rel_err_max": float(np.max(e))}
        return out


def build(sz, mix, seed, log):
    if mix["unit"] != "classify":
        raise ValueError(f"{sz['name']} runs the 'classify' unit, not "
                         f"{mix['unit']!r}")
    return Cell(sz, mix, seed, log)
