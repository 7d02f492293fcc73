"""Model FLOP/s utilization in %: FLOPs the model needs for one image
(forward and backward, no recomputation; counted by the reference's walk)
x images per second / (chips x the chip's published bf16 peak)."""


def read(record):
    peak = record["peaks"].get("bf16_flops")
    if not peak:
        return None
    run = record["run"]
    return (100.0 * record["model"]["flops_per_img"] * run["img_per_s"]
            / (run["chips"] * peak))
