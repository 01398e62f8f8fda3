"""The one generator of training traffic: a pool of distinct host batches
made from the seed, as a cell's file (`workloads/<cell>.json`) and its
configuration's `input` describe them.

  image:  x float32 (batch, H, W, C) standard normal, y float32 class ids
  tokens: x int32 (batch, T) ids below the vocabulary, y float32 the next
          token of each position (the last position takes the first id)

Every seed gives the same sizes; only the values change. The host arrays
are what the repo's iterators yield (float32 images, int32 ids), so the
host-to-device copy and the cast to bfloat16 are the program's.
"""
import numpy as np


def make_pool(cell, config, seed):
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    spec = config["input"]
    batch = int(cell["batch"])
    pool = []
    for _ in range(int(cell.get("pool", 8))):
        if spec["kind"] == "image":
            x = rng.standard_normal((batch,) + tuple(spec["shape"]),
                                    dtype=np.float32)
            y = rng.integers(0, spec["classes"], batch).astype(np.float32)
        elif spec["kind"] == "tokens":
            x = rng.integers(0, spec["vocab"], (batch, spec["length"]),
                             dtype=np.int32)
            y = np.roll(x, -1, axis=1).astype(np.float32)
        else:
            raise ValueError("unknown input kind %r" % spec["kind"])
        pool.append((x, y))
    return pool


def cycle(pool):
    """pool[0], pool[1], ... for ever: step i takes pool[i % len(pool)]."""
    i = 0
    while True:
        yield pool[i % len(pool)]
        i += 1
