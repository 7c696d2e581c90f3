"""Share of the window the fused engine's consumer spent blocked on a
staged chunk that was not ready (``StageStats.wait_s``)."""
LAYER = "fused engine staging (api/fused_engine.py, data/staging.py)"
UNIT = "%"
BETTER = "lower"
MOVES = "train_samples_per_s"


def read(facts):
    stage = facts["stage"]
    if not stage or "wait_s" not in stage or facts["window_s"] <= 0:
        return None
    return 100.0 * stage["wait_s"] / facts["window_s"]
