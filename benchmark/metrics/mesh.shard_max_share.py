"""mesh.shard_max_share: the busiest chip's share of the search, over
the window's checks that did not fail: the mean of ``max(shard_explored)
/ sum(shard_explored)``, from the per-chip configurations the key batch
reports under a mesh. 1/chips is an even split; 1.0 means one chip did
all of it."""


def read(run):
    shares = []
    for c in run.checks:
        split = None if c.failed else c.verdicts.get("shard_explored")
        if split and sum(split) > 0:
            shares.append(max(split) / sum(split))
    return sum(shares) / len(shares) if shares else None
