"""Small sizes of the benchmark's cells for the CPU tests: every width
cut, every path the same."""
import types

from perfbench import loader, run, traffic
from perfbench.peaks import PEAKS

BENCH = loader.benchmark()


def resnet():
    sz = dict(loader.sizes(BENCH, "resnet18-imagenet"), n_classes=10,
              in_hw=16, reference_images=4,
              calibration={"images": 8, "spread": 3.0})
    m = dict(traffic.load_mix("eval"), batch=2, hw=16, distinct_batches=2)
    return sz, m


def run_tiny(cell, sz, mix, seconds=0.5):
    """A whole run after the look for a chip, at small sizes; returns
    (result line, the cell object) so a test can read the controls."""
    wl = loader.workload(BENCH, cell)
    real = loader.config_module(wl["config"])
    built = []

    def build(*a):
        built.append(real.build(*a))
        return built[-1]

    mod = types.SimpleNamespace(build=build, unit_calls=real.unit_calls,
                                unit_flops=real.unit_flops)
    res = run.run_cell(BENCH, wl, sz, mix, loader.limits(cell), mod,
                       seed=2**40 + 3, seconds=seconds, traced=False,
                       peaks=PEAKS["TPU v5 lite"], dev=run.device_info())
    return res, built[0]
