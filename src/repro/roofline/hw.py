"""Hardware constants.

``PEAK_FLOPS_BF16_BY_KIND`` holds published per-chip peaks keyed by
``jax.Device.device_kind``; code that times real steps on a device takes its
peak from there (``peak_flops_bf16``).  The ``MODELLED_*`` and remaining
constants describe a TPU v5e for the analytic models (dry-run roofline,
calibration bridge, cluster power model), which run without a chip.
"""

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
PEAK_FLOPS_BF16_BY_KIND = {"TPU v5 lite": 197e12}


def peak_flops_bf16(device_kind: str) -> float:
    """Published bf16 peak FLOP/s of one chip of ``device_kind``."""
    try:
        return PEAK_FLOPS_BF16_BY_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device kind {device_kind!r}; "
            f"known: {sorted(PEAK_FLOPS_BF16_BY_KIND)}"
        ) from None


MODELLED_PEAK_FLOPS_BF16 = PEAK_FLOPS_BF16_BY_KIND["TPU v5 lite"]
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
HBM_BYTES = 16 * 2**30  # v5e HBM capacity per chip

# power model (used by the TPU flavour of the cluster simulator)
CHIP_IDLE_W = 60.0
CHIP_PEAK_W = 220.0
HOST_IDLE_W = 250.0  # per-host (CPU tray) idle
HOST_PEAK_W = 450.0
CHIPS_PER_HOST = 8

# host input-pipeline capacity per tray (Synergy-style disaggregated
# resources): sustained throughput of each pipeline stage at 100% of the
# stage, in *text-equivalent tokens/s* — per-family weights in
# ``roofline.analysis.analytic_host_profile`` rescale modality-heavy
# inputs (image patches, audio frames) into this unit
HOST_CPU_TOKENS_PER_S = 5.0e4  # tokenize / augment / batch / collate
HOST_DRAM_TOKENS_PER_S = 1.2e5  # staging copies (fetch->pin->DMA chain)
HOST_LOADER_TOKENS_PER_S = 8.0e4  # storage fetch + shard decode
