"""Local training's share of the chips' bf16 peak: forward and backward
operations of every example trained in a round (counted from the
shapes by the configuration's reference) times rounds per second of the
traced window, over chips times peak."""


def read(v):
    tr = v.cell.params
    per_user = max(1, tr["examples_per_user"] // tr["batch_size"]) \
        * tr["batch_size"] * tr["local_epochs"]
    rows = tr["k"] if tr["round_mode"] == "sparse" else tr["users"]
    flops = rows * per_user * v.flops_per_example * v.rounds
    return 100.0 * flops / v.window_s / (
        v.chips * v.peak["bf16_flops_per_s"])
