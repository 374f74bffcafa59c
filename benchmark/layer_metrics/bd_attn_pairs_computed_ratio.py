"""Pairs (query, key) the attention core computes over the pairs the
block-diffusion mask allows, one (sequence, head): the program's own
statement of its core (`describe`, what the `zoo_moe` journal event
carries) — tiles visited times the tile's area over `L (L + B)`. 1 is a
core that computes nothing it masks; at 512-wide tiles and B = 4 the 80
visited tiles hold 1.25 times the allowed pairs. None where the program
has no such model or statement."""

from benchmark import common


def read(run):
    try:
        said = common.build_model(run.ctx.config).describe(
            run.counters["batch_per_chip"] * run.ctx.config["input"][0],
            run.ctx.config["input"][0], run.device["platform"])
        return (said["attention_tiles_visited"] * said["attention_tile"] ** 2
                / said["attention_pairs_allowed"])
    except (ImportError, AttributeError, KeyError, TypeError):
        return None
