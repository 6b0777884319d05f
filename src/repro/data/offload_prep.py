"""OffloadPrep (paper §V): offload minibatch image preprocessing to the
storage node and/or peer initiators through OffloadFS — no scheduler, just
the FS's admission control. The dataset lives as image files on the
disaggregated volume; the initiator partitions each minibatch into a local
share and offloaded shares; the offloaded stub reads image blocks on the
target (near-data), preprocesses there, and returns only the (small)
normalized tensors.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core.fs import OffloadFS
from repro.core.offloader import TaskOffloader
from repro.data.preprocess import encode_image, preprocess_image, synthetic_image


def stub_preprocess(io, images: List[dict], out_size: int) -> List[np.ndarray]:
    """Target-side stub: images = [{"runs", "size", "seed"}]."""
    out = []
    for im in images:
        buf = b"".join(io.offload_read(b, n) for b, n in im["runs"])[: im["size"]]
        out.append(preprocess_image(buf, im["seed"], out_size))
    return out


class OffloadPrep:
    def __init__(self, fs: OffloadFS, offloader: Optional[TaskOffloader],
                 *, out_size: int = 224, offload_ratio: float = 1 / 3,
                 targets: Optional[Sequence[str]] = None):
        self.fs = fs
        self.off = offloader
        self.out_size = out_size
        self.offload_ratio = offload_ratio
        # None → follow the offloader's LIVE target registry (shards/peers
        # added later via add_target get prep shares too)
        self._targets = list(targets) if targets is not None else None
        if offloader is not None:
            offloader.register_local_stub("preprocess", stub_preprocess)
        # DISJOINT outcome counters — every image lands in exactly one, so
        # sum(stats.values()) == images processed:
        #   local     — planned for the initiator (never submitted)
        #   offloaded — ran on its planned remote target
        #   rerouted  — pushed back by the planned target, ran on another
        #   rejected  — pushed back and fell back to the initiator
        self.stats = {"local": 0, "offloaded": 0, "rejected": 0, "rerouted": 0}

    @property
    def targets(self) -> List[str]:
        if self._targets is not None:
            return self._targets
        return list(self.off.targets) if self.off else ["storage0"]

    # ------------------------------------------------------------ dataset
    def materialize_corpus(self, n_images: int, prefix: str = "/img",
                           seed: int = 0, max_side: int = 512) -> List[str]:
        paths = []
        for i in range(n_images):
            img = synthetic_image(seed * 100003 + i, max_side=max_side)
            p = f"{prefix}/{i:08d}.raw"
            self.fs.create(p)
            self.fs.write(p, encode_image(img), 0)
            paths.append(p)
        return paths

    # ---------------------------------------------------------- minibatch
    @staticmethod
    def _image_seed(epoch_seed: int, i: int) -> int:
        """Per-image augmentation seed, folded into RandomState's 32-bit
        domain (large epoch seeds — e.g. the PrepPipeline's per-batch
        seeds — must not overflow it). Values small callers pass are
        unchanged by the mod."""
        return (epoch_seed * 1000003 + i) % (2**31 - 1)

    def _image_arg(self, path: str, seed: int) -> Tuple[dict, list]:
        ino = self.fs.stat(path)
        return (
            {
                "runs": [(e.block, e.nblocks) for e in ino.extents],
                "size": ino.size,
                "seed": seed,
            },
            ino.extents,
        )

    def plan_shares(self, n: int) -> Tuple[List[Tuple[str, List[int]]],
                                           List[int]]:
        """Partition minibatch indices [0, n): ``offload_ratio × n`` images
        per remote target, the rest local. Returns (remote_shares,
        local_ids) where remote_shares is [(target, ids)]."""
        per_target = int(n * self.offload_ratio)
        remote: List[Tuple[str, List[int]]] = []
        idx = 0
        if self.off is not None and per_target > 0:
            for t in self.targets:
                ids = list(range(idx, min(idx + per_target, n)))
                if ids:
                    remote.append((t, ids))
                idx += per_target
        return remote, list(range(idx, n))

    def share_spec(self, target: str, ids: Sequence[int],
                   paths: Sequence[str], *, epoch_seed: int = 0,
                   reroute: bool = False) -> dict:
        """A ``TaskOffloader.submit`` spec for one remote share."""
        args, extents = [], []
        for i in ids:
            a, e = self._image_arg(paths[i], self._image_seed(epoch_seed, i))
            args.append(a)
            extents.extend(e)
        return {
            "task": "preprocess", "args": (args, self.out_size),
            "read_extents": extents, "write_extents": [],
            "target": target, "reroute": reroute,
            "mtime": max(self.fs.stat(paths[i]).mtime for i in ids),
        }

    def local_images(self, paths: Sequence[str], ids: Sequence[int], *,
                     epoch_seed: int = 0) -> List[np.ndarray]:
        """Preprocess the local share on the initiator (counted ``local``)."""
        out = [
            preprocess_image(self.fs.read(paths[i]),
                             self._image_seed(epoch_seed, i), self.out_size)
            for i in ids
        ]
        self.stats["local"] += len(ids)
        return out

    def note_remote_outcome(self, n: int, planned: str, ran: str) -> None:
        """Fold a remote share's resolution into the disjoint counters."""
        if self.off is not None and ran == self.off.node:
            self.stats["rejected"] += n
        elif ran != planned:
            self.stats["rerouted"] += n
        else:
            self.stats["offloaded"] += n

    def preprocess_minibatch(self, paths: Sequence[str], *, epoch_seed: int = 0
                             ) -> np.ndarray:
        """Split the minibatch: offload_ratio × len(paths) images per remote
        target, the rest locally. Returns (N, out, out, 3) f32."""
        n = len(paths)
        remote, local_ids = self.plan_shares(n)
        out: List[Optional[np.ndarray]] = [None] * n
        # remote shares: one submit round — one wire batch per target,
        # targets served concurrently (instead of serial per-target calls)
        specs = [self.share_spec(t, ids, paths, epoch_seed=epoch_seed)
                 for t, ids in remote]
        if specs:
            for (target, ids), (tensors, where) in zip(
                    remote, self.off.submit(specs)):
                self.note_remote_outcome(len(ids), target, where)
                for i, t in zip(ids, tensors):
                    out[i] = t
        for i, t in zip(local_ids,
                        self.local_images(paths, local_ids,
                                          epoch_seed=epoch_seed)):
            out[i] = t
        return np.stack(out)  # type: ignore[arg-type]
