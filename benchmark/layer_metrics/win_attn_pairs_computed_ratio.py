"""Pairs (query, key) a window layer's attention core computes over the
pairs its window allows, one (sequence, head): the program's own statement
of its cores (`describe`, what the `zoo_moe` journal event carries) —
tiles visited times the tile's area over the pairs allowed, of the
`sliding_attention` kind. 1 is a core that computes nothing it masks; at
512-wide tiles a window of 2,048 over 16,384 positions visits 150 tiles
that hold 1.25 times the allowed pairs; a core that walked the whole
causal triangle would read 4.4. None where the program has no such model
or statement."""

from benchmark import common

KIND = "sliding_attention"


def read(run):
    try:
        said = common.build_model(run.ctx.config).describe(
            run.counters["batch_per_chip"] * run.ctx.config["input"][0],
            run.ctx.config["input"][0], run.device["platform"])
        return (said["attention_tiles_visited_by_kind"][KIND]
                * said["attention_tile"] ** 2
                / said["attention_pairs_allowed_by_kind"][KIND])
    except (ImportError, AttributeError, KeyError, TypeError):
        return None
