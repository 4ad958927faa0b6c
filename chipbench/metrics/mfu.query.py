"""Forward FLOPs of every row the ticks served over the window's seconds
times the chips times the chip's bf16 peak, in percent."""


def read(rd):
    peak = rd["peaks"][rd["device_kind"]]["bf16_flops_per_s"]
    if not rd["flops_served"]:
        return None
    return 100.0 * rd["flops_served"] / (rd["seconds"] * rd["chips"] * peak)
