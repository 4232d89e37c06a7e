"""Datasets: CIFAR-10/100, ImageNet (folder), and synthetic stand-ins.

Parity targets: ``torchpack.mtpack.datasets.vision.{CIFAR, ImageNet}``
(referenced at /root/reference/configs/cifar/__init__.py:3 and
configs/imagenet/__init__.py:3). A dataset is a dict-like of splits
('train', 'test'); each split exposes ``__len__`` and
``get_batch(indices) -> (images f32 NHWC, labels i32)`` with the split's
transform (augment+normalize for train, normalize for eval) applied.

Everything is numpy host-side; batches stream to the device already collated.
CIFAR reads the standard python pickle batches directly (no torchvision in
this environment); ImageNet scans a class-per-directory tree and decodes with
PIL. Both fall back to a deterministic synthetic split when the data root is
missing and ``synthetic_fallback`` is set — keeping smoke tests and benches
runnable on machines without the datasets.
"""

import os
import pickle
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from dgc_tpu.data.native import crop_flip_normalize
from dgc_tpu.telemetry import trace as _trace

__all__ = ["ArraySplit", "SyntheticSplit", "SyntheticTokenSplit", "CIFAR",
           "ImageNet", "Synthetic", "SyntheticTokens",
           "CIFAR_MEAN", "CIFAR_STD", "IMAGENET_MEAN", "IMAGENET_STD"]

CIFAR_MEAN = np.array([0.4914, 0.4822, 0.4465], np.float32)
CIFAR_STD = np.array([0.2470, 0.2435, 0.2616], np.float32)
IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


def _normalize(images_u8: np.ndarray, mean: np.ndarray,
               std: np.ndarray) -> np.ndarray:
    return (images_u8.astype(np.float32) / 255.0 - mean) / std


def _random_crop_flip_reference(images_u8: np.ndarray, ys, xs, flips,
                                pad: int) -> np.ndarray:
    """Per-image oracle for the fused kernels in ``dgc_tpu.data.native``
    (zero-pad + crop at (ys, xs) + horizontal flip) — tests only."""
    n, h, w, c = images_u8.shape
    padded = np.zeros((n, h + 2 * pad, w + 2 * pad, c), images_u8.dtype)
    padded[:, pad:pad + h, pad:pad + w] = images_u8
    out = np.empty_like(images_u8)
    for i in range(n):
        img = padded[i, ys[i]:ys[i] + h, xs[i]:xs[i] + w]
        out[i] = img[:, ::-1] if flips[i] else img
    return out


class ArraySplit:
    """In-memory split over uint8 NHWC images."""

    def __init__(self, images: np.ndarray, labels: np.ndarray,
                 mean: np.ndarray, std: np.ndarray, train: bool,
                 pad: int = 4, augment: bool = True, seed: int = 0):
        self.images = images
        self.labels = labels.astype(np.int32)
        self.mean = mean
        self.std = std
        self.train = train
        self.pad = pad
        self.augment = augment
        self._rng = np.random.RandomState(seed)

    def __len__(self) -> int:
        return len(self.images)

    def get_batch(self, indices: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
        with _trace.span("input.get_batch", images=len(indices)):
            imgs = self.images[indices]
            if self.train and self.augment:
                n = len(imgs)
                ys = self._rng.randint(0, 2 * self.pad + 1, size=n)
                xs = self._rng.randint(0, 2 * self.pad + 1, size=n)
                flips = self._rng.randint(0, 2, size=n).astype(np.uint8)
                return (crop_flip_normalize(imgs, ys, xs, flips, self.pad,
                                            self.mean, self.std),
                        self.labels[indices])
            return (_normalize(imgs, self.mean, self.std),
                    self.labels[indices])


class SyntheticSplit:
    """Deterministic random data shaped like the real thing — for tests and
    machine-local benches (no dataset download in this environment)."""

    def __init__(self, n: int, image_size: int, num_classes: int,
                 mean: np.ndarray, std: np.ndarray, seed: int = 0,
                 train: bool = True):
        # class-prototype images + noise: a STRUCTURED, learnable task.
        # (Labels derived from pixel hashes look random to a conv net —
        # exactly the adversarial case for importance-sampled sparsity —
        # so convergence comparisons on such data are meaningless.)
        # The prototype seed is split-independent: train and test share
        # classes, so eval accuracy is a real generalization signal.
        proto_rng = np.random.RandomState(10_000 + num_classes)
        protos = proto_rng.randn(
            num_classes, image_size, image_size, 3).astype(np.float32)
        rng = np.random.RandomState(seed)
        self.labels = rng.randint(0, num_classes, n).astype(np.int32)
        raw = protos[self.labels] + 1.5 * rng.randn(
            n, image_size, image_size, 3).astype(np.float32)
        # FIXED quantization window (+-4 sigma of proto+noise, std
        # sqrt(1+1.5^2)): per-split min/max would normalize train and test
        # on slightly different scales, a covariate shift masquerading as
        # a generalization gap
        k = 4.0 * float(np.sqrt(1.0 + 1.5 ** 2))
        self.images = (np.clip((raw + k) / (2 * k), 0.0, 1.0)
                       * 255).astype(np.uint8)
        self.mean, self.std = mean, std

    def __len__(self) -> int:
        return len(self.images)

    def get_batch(self, indices: np.ndarray):
        with _trace.span("input.get_batch", images=len(indices)):
            return (_normalize(self.images[indices], self.mean, self.std),
                    self.labels[indices])


class SyntheticTokenSplit:
    """Rows of ``seq_len`` token ids with their next-token labels, made
    from the seed (no corpus in this environment). A token follows its
    predecessor by a fixed random successor table half of the time and is
    drawn from a Zipf distribution (P(rank) ~ 1 / rank) otherwise: a
    STRUCTURED, learnable stream, as :class:`SyntheticSplit`'s images are.
    ``get_batch(indices)`` gives ``(inputs int32 [n, seq_len], labels
    int32 [n * seq_len])``: labels are token-major, as a token model's
    logits are, so the batch axis still shards by sequence and the step's
    micro-batch cut takes labels of one axis (training/step.py)."""

    def __init__(self, n: int, seq_len: int, vocab_size: int, seed: int = 0):
        rng = np.random.RandomState(seed)
        # the successor table is split-independent: train and test share
        # the language
        follows = np.random.RandomState(20_000 + vocab_size).permutation(
            vocab_size).astype(np.int32)
        cdf = np.cumsum(1.0 / np.arange(1, vocab_size + 1))
        # every position's Zipf draw, then in order of t: half of them
        # replaced by the successor of the token before
        rows = np.searchsorted(
            cdf, rng.random_sample((n, seq_len + 1)) * cdf[-1], side="right"
        ).clip(max=vocab_size - 1).astype(np.int32)
        follow = rng.random_sample((n, seq_len + 1)) < 0.5
        for t in range(1, seq_len + 1):
            rows[:, t] = np.where(follow[:, t], follows[rows[:, t - 1]],
                                  rows[:, t])
        self.rows = rows

    def __len__(self) -> int:
        return len(self.rows)

    def get_batch(self, indices: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
        with _trace.span("input.get_batch", sequences=len(indices)):
            rows = self.rows[indices]
            return (np.ascontiguousarray(rows[:, :-1]),
                    np.ascontiguousarray(rows[:, 1:]).reshape(-1))


def SyntheticTokens(seq_len: int = 2048, vocab_size: int = 32000,
                    synthetic_size: int = 64, seed: int = 0
                    ) -> Dict[str, object]:
    """A token dataset: ``synthetic_size`` training rows and a quarter as
    many test rows of ``seq_len`` tokens over ``vocab_size`` ids."""
    return {
        "train": SyntheticTokenSplit(synthetic_size, seq_len, vocab_size,
                                     seed=seed),
        "test": SyntheticTokenSplit(max(synthetic_size // 4, 1), seq_len,
                                    vocab_size, seed=seed + 1),
    }


def CIFAR(root: str, num_classes: int = 10, image_size: int = 32,
          synthetic_fallback: bool = True, synthetic_size: int = 2048,
          seed: int = 0) -> Dict[str, object]:
    """CIFAR-10/100 from the standard python pickle batches."""
    name = "cifar-10-batches-py" if num_classes == 10 else "cifar-100-python"
    base = os.path.join(root, name)
    if not os.path.isdir(base):
        if os.path.isdir(root) and any(
                f.startswith("data_batch") for f in os.listdir(root)):
            base = root
        elif synthetic_fallback:
            return Synthetic(num_classes=num_classes, image_size=image_size,
                             n_train=synthetic_size,
                             n_test=max(synthetic_size // 4, 256),
                             mean=CIFAR_MEAN, std=CIFAR_STD, seed=seed)
        else:
            raise FileNotFoundError(f"CIFAR data not found under {root}")

    def load(files: Sequence[str]):
        xs, ys = [], []
        for f in files:
            with open(os.path.join(base, f), "rb") as fh:
                d = pickle.load(fh, encoding="bytes")
            xs.append(d[b"data"])
            ys.append(d.get(b"labels", d.get(b"fine_labels")))
        x = np.concatenate(xs).reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        y = np.concatenate([np.asarray(y) for y in ys])
        return np.ascontiguousarray(x), y

    if num_classes == 10:
        train_x, train_y = load([f"data_batch_{i}" for i in range(1, 6)])
        test_x, test_y = load(["test_batch"])
    else:
        train_x, train_y = load(["train"])
        test_x, test_y = load(["test"])

    return {
        "train": ArraySplit(train_x, train_y, CIFAR_MEAN, CIFAR_STD,
                            train=True, seed=seed),
        "test": ArraySplit(test_x, test_y, CIFAR_MEAN, CIFAR_STD,
                           train=False),
    }


def _decode_one(args):
    """Decode+augment one image — a module-level function so a worker
    POOL can run it (the DataLoader-num_workers role, reference
    train.py:96-107). Augmentation randomness comes from an explicit
    per-image seed, so results are identical whether decoded inline, by a
    pool, or in any order."""
    from PIL import Image
    path, s, train, seed = args
    rng = np.random.RandomState(seed)
    img = Image.open(path).convert("RGB")
    if train:
        # RandomResizedCrop-style: random scale/aspect crop then resize
        w, h = img.size
        area = w * h
        for _ in range(10):
            target = rng.uniform(0.08, 1.0) * area
            ar = np.exp(rng.uniform(np.log(3 / 4), np.log(4 / 3)))
            cw = int(round(np.sqrt(target * ar)))
            ch = int(round(np.sqrt(target / ar)))
            if cw <= w and ch <= h:
                x = rng.randint(0, w - cw + 1)
                y = rng.randint(0, h - ch + 1)
                img = img.crop((x, y, x + cw, y + ch)).resize((s, s))
                break
        else:
            img = img.resize((s, s))
        arr = np.asarray(img, np.uint8)
        if rng.randint(2):
            arr = arr[:, ::-1]
    else:
        # resize shorter side to 1.143*s then center crop (256/224 recipe)
        w, h = img.size
        short = int(s * 256 / 224)
        if w < h:
            img = img.resize((short, int(h * short / w)))
        else:
            img = img.resize((int(w * short / h), short))
        w, h = img.size
        x, y = (w - s) // 2, (h - s) // 2
        img = img.crop((x, y, x + s, y + s))
        arr = np.asarray(img, np.uint8)
    return arr


class _ImageFolderSplit:
    """Class-per-directory ImageNet split, decoded by a persistent process
    pool (the torch DataLoader ``num_workers`` role, reference
    train.py:96-107). At the reference step rate (bs 32 at ~25 ms/step),
    the pipeline must sustain >~1300 img/s; single-threaded PIL decodes a
    fraction of that, so ``workers`` defaults to the host's core count
    (clamped) and ``get_batch`` fans the per-image decode+augment out over
    the pool. Per-image seeds keep the output bitwise independent of the
    worker count and of completion order."""

    #: upper bound on the default pool size — decode throughput saturates
    #: well before the largest TPU-VM hosts' 100+ cores
    MAX_DEFAULT_WORKERS = 32

    def __init__(self, root: str, image_size: int, train: bool,
                 seed: int = 0, workers: Optional[int] = None):
        from PIL import Image  # noqa: F401 — fail fast if PIL missing
        self.root = root
        self.image_size = image_size
        self.train = train
        self._rng = np.random.RandomState(seed)
        if workers is None:
            workers = min(os.cpu_count() or 1, self.MAX_DEFAULT_WORKERS)
        self.workers = max(1, int(workers))
        self._pool = None
        classes = sorted(d for d in os.listdir(root)
                         if os.path.isdir(os.path.join(root, d)))
        self.class_to_idx = {c: i for i, c in enumerate(classes)}
        self.samples = []
        for c in classes:
            cdir = os.path.join(root, c)
            for f in sorted(os.listdir(cdir)):
                self.samples.append((os.path.join(cdir, f),
                                     self.class_to_idx[c]))

    def __len__(self) -> int:
        return len(self.samples)

    def _get_pool(self):
        if self._pool is None and self.workers > 1:
            import multiprocessing as mp
            # spawn, not fork: the parent runs multithreaded JAX and
            # fork()ing it risks deadlock; decode workers need no parent
            # state (the decode fn is module-level and self-contained)
            self._pool = mp.get_context("spawn").Pool(self.workers)
        return self._pool

    def close(self):
        if self._pool is not None:
            self._pool.terminate()
            self._pool.join()
            self._pool = None

    def __del__(self):  # best effort; close() is the real API
        try:
            self.close()
        except Exception:
            pass

    def get_batch(self, indices: np.ndarray):
        # one sequential draw per batch keeps the master RNG stream
        # identical regardless of pool size or completion order
        seeds = self._rng.randint(0, 2 ** 31 - 1, size=len(indices))
        args = [(self.samples[i][0], self.image_size, self.train, int(sd))
                for i, sd in zip(indices, seeds)]
        pool = self._get_pool()
        if pool is not None:
            decoded = pool.map(_decode_one, args,
                               chunksize=max(1, len(args) // self.workers))
        else:
            decoded = [_decode_one(a) for a in args]
        imgs = np.stack(decoded)
        labels = np.asarray([self.samples[i][1] for i in indices], np.int32)
        return _normalize(imgs, IMAGENET_MEAN, IMAGENET_STD), labels


def ImageNet(root: str, num_classes: int = 1000, image_size: int = 224,
             synthetic_fallback: bool = True, synthetic_size: int = 512,
             seed: int = 0) -> Dict[str, object]:
    train_dir = os.path.join(root, "train")
    val_dir = os.path.join(root, "val")
    if not (os.path.isdir(train_dir) and os.path.isdir(val_dir)):
        if synthetic_fallback:
            return Synthetic(num_classes=num_classes, image_size=image_size,
                             n_train=synthetic_size,
                             n_test=max(synthetic_size // 4, 64),
                             mean=IMAGENET_MEAN, std=IMAGENET_STD, seed=seed)
        raise FileNotFoundError(f"ImageNet train/val not found under {root}")
    return {
        "train": _ImageFolderSplit(train_dir, image_size, train=True,
                                   seed=seed),
        "test": _ImageFolderSplit(val_dir, image_size, train=False),
    }


def Synthetic(num_classes: int = 10, image_size: int = 32,
              n_train: int = 2048, n_test: int = 512,
              mean: np.ndarray = CIFAR_MEAN, std: np.ndarray = CIFAR_STD,
              seed: int = 0) -> Dict[str, object]:
    return {
        "train": SyntheticSplit(n_train, image_size, num_classes, mean, std,
                                seed=seed, train=True),
        "test": SyntheticSplit(n_test, image_size, num_classes, mean, std,
                               seed=seed + 1, train=False),
    }
