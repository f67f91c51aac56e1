"""The FedFly migration checkpoint (paper §IV, "Model data checkpoint").

The source edge server checkpoints, per moving device:
  epoch number, gradients, model weights, loss value, optimizer state
plus (framework additions, required for exact resume):
  round number, batch index inside the epoch, split point, RNG counter,
  data-loader identity — so the destination resumes *the exact batch*.

The checkpoint is a plain pytree serialized with the versioned,
pickle-free codec in ``repro.runtime.serialization`` (raw = bit-exact,
int8 = quantized payload for the beyond-paper overhead optimization; the
int8 codec never touches the integer bookkeeping leaves).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.obs import telemetry as obs
from repro.runtime import serialization

Params = Any


@dataclass
class EdgeCheckpoint:
    """Everything the destination edge server needs to resume training of
    one device's server-side stage mid-round."""

    client_id: str
    round_idx: int
    epoch: int
    batch_idx: int
    split_point: int
    server_params: Params
    optimizer_state: Params
    last_grads: Optional[Params] = None     # paper lists gradients explicitly
    loss: float = 0.0
    rng_seed: int = 0
    meta: Dict[str, Any] = field(default_factory=dict)

    # -- serialization ------------------------------------------------------

    def to_tree(self) -> Dict[str, Any]:
        scalars = {
            "client_id": np.frombuffer(
                self.client_id.encode().ljust(64, b"\0")[:64], np.uint8).copy(),
            "round_idx": np.int64(self.round_idx),
            "epoch": np.int64(self.epoch),
            "batch_idx": np.int64(self.batch_idx),
            "split_point": np.int64(self.split_point),
            "loss": np.float64(self.loss),
            "rng_seed": np.int64(self.rng_seed),
        }
        arrays = (self.server_params, self.optimizer_state, self.last_grads)
        on_device = sum(x.nbytes for x in jax.tree.leaves(arrays)
                        if isinstance(x, jax.Array))
        with obs.span("mig.fetch", bytes=on_device):   # device to host
            tree: Dict[str, Any] = {
                "scalars": scalars,
                "server_params": jax.tree.map(np.asarray, self.server_params),
                "optimizer_state": jax.tree.map(np.asarray,
                                                self.optimizer_state),
            }
            if self.last_grads is not None:
                tree["last_grads"] = jax.tree.map(np.asarray, self.last_grads)
        return tree

    @classmethod
    def from_tree(cls, tree: Dict[str, Any]) -> "EdgeCheckpoint":
        s = tree["scalars"]
        return cls(
            client_id=bytes(s["client_id"]).rstrip(b"\0").decode(),
            round_idx=int(s["round_idx"]),
            epoch=int(s["epoch"]),
            batch_idx=int(s["batch_idx"]),
            split_point=int(s["split_point"]),
            server_params=tree["server_params"],
            optimizer_state=tree["optimizer_state"],
            last_grads=tree.get("last_grads"),
            loss=float(s["loss"]),
            rng_seed=int(s["rng_seed"]),
        )

    def pack(self, codec: str = "raw", *, base=None,
             base_version: Optional[str] = None) -> bytes:
        """``base`` is a (possibly partial) tree mirroring ``to_tree()``
        — e.g. ``{"server_params": <round-start stage>}`` — that the
        delta codec encodes residuals against."""
        return serialization.pack_pytree(self.to_tree(), codec=codec,
                                         base=base,
                                         base_version=base_version)

    def pack_chunks(self, codec: str = "raw", *, base=None,
                    base_version: Optional[str] = None):
        """Incremental serialization for streamed transfers
        (``FrameStream.send_chunked``)."""
        return serialization.pack_pytree_chunks(
            self.to_tree(), codec=codec, base=base,
            base_version=base_version)

    @classmethod
    def unpack(cls, data: bytes, *, base=None) -> "EdgeCheckpoint":
        return cls.from_tree(serialization.unpack_pytree(data, base=base))

    @staticmethod
    def base_version_of(data: bytes) -> Optional[str]:
        """Which base version a received payload needs (None: none)."""
        return serialization.peek_base_version(data)

    def nbytes(self, codec: str = "raw", **kw) -> int:
        return len(self.pack(codec, **kw))

    def replace(self, **kw) -> "EdgeCheckpoint":
        return dataclasses.replace(self, **kw)
