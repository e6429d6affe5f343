"""Model FLOPs of the window's studies over the window's time and the
chip's bf16 peak, in %.  Counted from the configuration's forward FLOPs
per sample, training at three forward passes: each requester's fits on
its own shard and each contributor's refresh on its shard (drop-last
batches, so no padded row), and each requester's evaluation on its test
split, as many as the study ran.  The fits run at the default matmul
precision, one bf16 pass, so the bf16 peak is the ceiling."""


def study_flops(rec) -> float:
    t, conf = rec["traffic"], rec["conf"]
    knobs = t["method"]
    fwd = rec["model"].forward_flops(conf)
    b = knobs["batch_size"]
    lane_rounds = rec["rounds_executed"]              # over all requesters
    rounds = lane_rounds / rec["requesters"]
    fits = lane_rounds * knobs["epochs"] * (t["own_samples"] // b) * b * 3 * fwd
    refresh = (knobs["n_max"] * rounds * knobs["contributor_refresh_epochs"]
               * (conf["shard_samples"] // b) * b * 3 * fwd)
    evals = lane_rounds * conf["test_samples"] * fwd
    return fits + refresh + evals


def read(rec):
    if rec["peak"] is None:
        return None
    return (100.0 * study_flops(rec) * len(rec["studies"])
            / (rec["elapsed_s"] * rec["peak"]["bf16_flops"]))
