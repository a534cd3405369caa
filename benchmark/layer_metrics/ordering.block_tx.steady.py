"""Mean transactions per block over the blocks of the window, as fetched
for the cross-check (exact)."""


def read(obs):
    sizes = obs.get("block_sizes")
    return sum(sizes) / len(sizes) if sizes else None
