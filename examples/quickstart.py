"""Quickstart: the paper's workflow end-to-end, in five minutes.

1. A code generator describes a kernel by its address expressions (here: the
   paper's range-4 3D25pt star stencil).
2. The estimator predicts per-LUP data volumes at every memory level.
3. The multi-limiter roofline model turns them into a performance prediction.
4. The ranking explores the configuration space analytically (no compilation,
   no benchmarking, no GPU).
5. The same machinery, TPU-adapted, picks Pallas BlockSpec tilings.

Run: PYTHONPATH=src python examples/quickstart.py
"""
from repro.core import appspec, estimator, model, ranking
from repro.core.machine import V100

# -- 1+2: estimate one configuration ----------------------------------------
spec = appspec.star3d(block=(16, 2, 32))
est = estimator.estimate(spec, V100, method="sym")
print(f"config block=(16,2,32): L1 cycles/LUP     = {est.l1_cycles:.2f}")
print(f"                        L2->L1 load B/LUP = {est.v_l2l1_load:.1f}")
print(f"                        DRAM load B/LUP   = {est.v_dram_load:.1f}")
print(f"                        DRAM store B/LUP  = {est.v_dram_store:.1f}")

# -- 3: predict performance ---------------------------------------------------
pred = model.predict(spec, est, V100)
print(f"predicted: {pred.glups:.1f} GLup/s, limiter = {pred.limiter}")
print(f"paper's prediction for this config: 27.6 GLup/s, DRAM-limited\n")

# -- 4: rank the paper's 162-config space ------------------------------------
ranked = ranking.rank_configs(
    lambda block, fold: appspec.star3d(block=block, fold=fold),
    appspec.stencil_config_space(),
    machine=V100,  # registry: repro.core.machine.MACHINES (V100/A100/H100/...)
    method="sym",
)
print("top-5 of 162 configurations (evaluated analytically in seconds):")
for r in ranked[:5]:
    print(
        f"  block={r.config['block']} fold={r.config['fold']}: "
        f"{r.prediction.glups:.1f} GLup/s [{r.prediction.limiter}]"
    )
print(f"worst: block={ranked[-1].config['block']}: {ranked[-1].prediction.glups:.1f} GLup/s\n")

# -- 5: the TPU adaptation picks Pallas block shapes the same way -------------
from repro.core.machine import TPU_V5E
from repro.kernels.stencil25 import select_block

blk, test = select_block((256, 256, 512), r=4, machine=TPU_V5E)
print(
    f"TPU Pallas stencil tile for a 256x256x512 grid: {blk} "
    f"(VMEM {test.vmem_bytes >> 20} MiB, limiter {test.limiter}, "
    f"layout efficiency {test.layout_efficiency:.2f})"
)
