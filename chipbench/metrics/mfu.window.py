"""Model FLOPs of a window (``chipbench.flops.window_flops``) over the
window's seconds times the chips times the chip's bf16 peak, in percent."""


def read(rd):
    peak = rd["peaks"][rd["device_kind"]]["bf16_flops_per_s"]
    return 100.0 * rd["flops_per_window"] / (rd["window_s"] * rd["chips"]
                                             * peak)
