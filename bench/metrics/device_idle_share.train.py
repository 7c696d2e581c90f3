"""Share of the timed training window (fused or Sequential) in which no
operation ran on the device.  The device's busy time per round is the union
of device-operation intervals over the traced call, over its rounds; the
timed call runs the same compiled chunk, so its busy time is that times its
rounds: 1 - busy per round x rounds / the timed window's length."""
LAYER = "device (TPU v5e)"
UNIT = "%"
BETTER = "lower"
MOVES = "train_samples_per_s"


def read(facts):
    tr = facts["trace"]
    if tr is None or not tr.devices or tr.window_s <= 0:
        return None
    busy = tr.busy_s / facts["trace_rounds"] * facts["rounds"]
    return 100.0 * (1.0 - busy / facts["window_s"])
