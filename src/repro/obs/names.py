"""Canonical metric, scope and kernel names (DESIGN.md §12).

Every metric the serving engine, kernels, and benches emit, every
``jax.named_scope`` the deploy path opens and every Pallas kernel name
is named here, not at the emission site — the DESIGN.md §12 tables are
checked against this module by ``tools/check_metrics.py`` (CI docs job),
so a renamed or deleted name fails the build instead of silently
breaking a dashboard or a benchmark reader.

Naming scheme: dot-separated ``<plane>.<subsystem>.<what>``; histograms
of durations end in ``.seconds``. Prometheus exposition sanitizes dots
to underscores (``MetricsRegistry.to_prometheus``).
"""
from __future__ import annotations

# -- serving plane (recorded by repro.serve.engine.ServingEngine) -----------

#: counter: requests accepted by ``submit()``
REQUESTS_SUBMITTED = "serve.requests.submitted"
#: counter: requests finished and retired from their slot
REQUESTS_COMPLETED = "serve.requests.completed"
#: counter: decode tokens emitted across all slots
TOKENS_GENERATED = "serve.tokens.generated"
#: counter: in-service column-scale recalibrations landed
#: (``ServingEngine.recalibrate`` / eval/recalibrate.py)
RECALIBRATIONS = "serve.recalibrations"
#: gauge: requests waiting in the admission queue
QUEUE_DEPTH = "serve.queue.depth"
#: gauge: slots currently serving a live request
ACTIVE_SLOTS = "serve.slots.active"
#: histogram: submit -> admission wait per request
QUEUE_WAIT_SECONDS = "serve.request.queue_wait.seconds"
#: histogram: submit -> last token per request
REQUEST_LATENCY_SECONDS = "serve.request.latency.seconds"
#: histogram: per-request prefill span (all prompt tokens)
PREFILL_SECONDS = "serve.prefill.seconds"
#: histogram: one engine decode step (all active slots advance one token)
DECODE_STEP_SECONDS = "serve.decode.step.seconds"

# -- CIM / ADC plane (recorded by repro.obs.adc, fed from the kernels) ------

#: counter: kernel invocations folded by the sampled collector
ADC_SAMPLES = "cim.adc.samples"
#: counter: ADC conversions covered by the folded samples
ADC_CONVERSIONS = "cim.adc.conversions"
#: counter: conversions whose partial sum clipped at the ADC range
ADC_SATURATED = "cim.adc.saturated"
#: histogram: per-column saturation rate, one observation per column
#: per folded sample (the paper-native drift signal)
ADC_COL_SATURATION_RATE = "cim.adc.col_saturation_rate"
#: histogram: per-column mean ADC range occupancy |q|/q_max
ADC_OCCUPANCY = "cim.adc.occupancy"
#: counter: grid steps of the CIM kernels traced (``fused_grid_call``
#: records each kernel's grid size while it is traced, never inside the
#: compiled program; ``repro.obs.compiles`` counts it)
CIM_GRID_STEPS = "cim.grid.steps"
#: counter: bytes of the patch operands of the CIM convs traced
#: (``kernels.cim_conv.tile_major_patches`` records each conv's
#: (k_tiles, M, rows) operand while it is traced; ``repro.obs.compiles``
#: counts it)
CIM_PATCHES_BYTES = "cim.patches.bytes"

# -- vision plane (recorded by repro.models.vit while it is traced) ---------

#: counter: query-key pairs the vision tower's attention covers, the sum
#: of n_i^2 over the images of a traced forward (attention stays within
#: each image; one mask over the whole pack would cover (sum n_i)^2)
VIT_ATTN_PAIRS = "vit.attn.pairs"

# -- compile plane (recorded by repro.obs.compiles, from jax.monitoring) ----

#: counter: XLA backend compile requests — a fresh compile or a load from
#: the persistent compilation cache (``jit.cache.hits`` counts the loads)
JIT_COMPILES = "jit.compiles"
#: histogram: seconds per backend compile request
JIT_COMPILE_SECONDS = "jit.compile.seconds"
#: counter: compile requests served from the persistent compilation cache
JIT_CACHE_HITS = "jit.cache.hits"
#: counter: compiles written to the persistent compilation cache (JAX
#: records its miss event on the write; a compile under the cache's
#: minimum compile time is neither a hit nor a miss)
JIT_CACHE_MISSES = "jit.cache.misses"

# -- named scopes (``jax.named_scope``) --------------------------------------
#
# Trace-time metadata: each lands in the compiled HLO's
# ``metadata={op_name=...}`` path of the ops traced inside it (a fusion
# takes its root's), so device time in a profile can be put down to the
# layer that issued it. Nothing runs for them.

#: scope: activation codes and the dequant scale arithmetic of a CIM layer
SCOPE_ACT_QUANT = "cim.act_quant"
#: scope: stretched-kernel patch extraction: the deploy path's tile-major
#: builder (``kernels.cim_conv.tile_major_patches``) and the oracle's
#: (``ref.extract_conv_patches``)
SCOPE_PATCHES = "cim.patches"
#: scope: the deploy kernels' operand relayout — tile-major activations,
#: scale slabs, the occupancy table, padding before the kernel and the
#: slice after it
SCOPE_LAYOUT = "cim.layout"
#: scope: the deploy kernel's ``pallas_call``
SCOPE_KERNEL = "cim.kernel"
#: scope: the ResNet stem (full-precision conv, BN, ReLU)
SCOPE_RESNET_STEM = "resnet.stem"
#: scope template: one ResNet basic block; each of its convs nests a
#: scope named by the conv's parameter key (``conv1``, ``conv2``, ``proj``)
SCOPE_RESNET_BLOCK = "resnet.s{stage}b{block}"
#: scope: the ResNet head (average pool and classifier)
SCOPE_RESNET_HEAD = "resnet.head"
#: scope: the vision tower's float patch embedding and position table
SCOPE_VIT_PATCH_EMBED = "vit.patch_embed"
#: scope: one vision-tower block (its CIM linears keep their ``cim.*``
#: scopes inside it)
SCOPE_VIT_BLOCK = "vit.block"
#: scope: the vision tower's attention core between the CIM linears:
#: 2-D RoPE, QK, softmax and AV, per image
SCOPE_VIT_ATTN = "vit.attn"
#: scope: the 2x2 patch merge before the projector
SCOPE_VIT_MERGE = "vit.merge"

# -- Pallas kernel names (``pallas_call(name=...)``) -------------------------
#
# The device trace names a kernel's op after this name plus a counter
# (``cim_matmul.3``); every CIM kernel's name starts with ``cim_``.

#: kernel: the fused CIM matmul with the column ADC
KERNEL_CIM_MATMUL = "cim_matmul"
#: kernel: the ADC-free CIM matmul (digital psum accumulation)
KERNEL_CIM_ADC_FREE = "cim_adc_free"
#: kernel: the batched MoE expert-bank CIM matmul
KERNEL_CIM_EXPERTS = "cim_experts"
