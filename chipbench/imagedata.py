"""Traffic of a cell fed by an input pipeline: a seeded pool of decoded
images, the transform of one sample, and the plain recomputation of a
whole batch that ``correct`` holds the loader's batches to.

NumPy alone, nothing of the program.  The parameters (``pool_images``,
``dataset_length``, ``flip_p``, ``mean``, ``std``, the loader's
arguments) are the workload file's ``traffic_params``; a new mix of
them is a new data file and no code.
"""
import numpy as onp


def make_pool(seed, n, image, classes):
    """(uint8 images [n, image, image, 3] HWC, int32 labels [n]) from the
    seed: a decoded-image cache, as a RecordIO of pre-resized images is
    read.  Bytes in bulk; a seed may pass 2**31."""
    rng = onp.random.default_rng([int(seed), 3])
    size = n * image * image * 3
    raw = rng.bit_generator.random_raw(-(-size // 8))   # 30x Generator.bytes
    images = raw.view(onp.uint8)[:size].reshape(n, image, image, 3)
    return images, rng.integers(0, classes, n, dtype=onp.int32)


def flipped(seed, i, p):
    """Whether sample ``i`` is mirrored: a generator of its own, seeded
    from (seed, i), so the decision follows the sample into whichever
    worker draws it."""
    return bool(onp.random.default_rng([int(seed), int(i)]).random() < p)


def transform(image, flip, mean, std):
    """One sample as the training script hands it to the network: flip,
    float32 / 255, normalise; layout kept HWC."""
    if flip:
        image = image[:, ::-1]
    x = image.astype(onp.float32) / onp.float32(255)
    return (x - mean) / std


def sampler_order(seed, length):
    """The order ``gluon.data.RandomSampler`` draws once NumPy's global
    generator has been seeded with ``numpy_seed(seed)``."""
    return onp.random.RandomState(numpy_seed(seed)).permutation(length)


def numpy_seed(seed):
    """``--seed`` as ``numpy.random.seed`` takes it (under 2**32)."""
    return int(seed) % (1 << 32)


def batch_indices(order, k, batch):
    """Dataset indices of the loader's ``k``-th batch."""
    return order[k * batch:(k + 1) * batch]


def recompute_batch(pool, indices, seed, tp):
    """(float32 [b, h, w, 3], int32 [b]): the batch over ``indices``,
    worked out for all rows at once -- the same arithmetic in the same
    type as ``transform``, so a sound loader's batch equals it bit for
    bit."""
    images, labels = pool
    at = onp.asarray(indices) % len(images)
    raw = images[at]
    flips = onp.array([flipped(seed, i, tp["flip_p"]) for i in indices])
    raw[flips] = raw[flips][:, :, ::-1]
    x = raw.astype(onp.float32) / onp.float32(255)
    mean = onp.asarray(tp["mean"], onp.float32)
    std = onp.asarray(tp["std"], onp.float32)
    return (x - mean) / std, labels[at]
