"""Model FLOP/s utilization of training, fused or Sequential: the round's
forward and backward operations (``bench/flops``) times the timed window's
rounds, over its length, over the chip's bf16 peak (float32 at default
precision runs its products as bf16 passes, so bf16 is the ceiling)."""
LAYER = "round program (core/spmd.py cohort step, models/resnet.py)"
UNIT = "%"
BETTER = "higher"
MOVES = "train_samples_per_s"


def read(facts):
    pk = facts["peaks"]
    if pk is None or facts["window_s"] <= 0:
        return None
    rate = facts["count"].flops * facts["rounds"] / facts["window_s"]
    return 100.0 * rate / (pk["bf16_flops_per_s"] * facts["devices"])
