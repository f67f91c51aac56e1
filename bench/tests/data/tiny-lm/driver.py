"""Test-only driver: a small token model through the testbed driver.

It loads ``drivers/testbed.py`` and overrides the hooks that depend on
the model (the configuration check, the model, the clients' batchers
and the merge of the two stages); the scheduler, the optimizer, the
instrumentation and the window are the testbed's. The harness's tests
copy it to ``bench/drivers/tinylm.py`` of a scratch checkout.
"""
from __future__ import annotations

from pathlib import Path
from typing import Any, Dict, List

import numpy as np

import data
import harness

testbed = harness.driver("testbed", Path(__file__).resolve().parents[2])


class TokenBatcher:
    """Rows of ``seq_len + 1`` tokens. A batch is ``batch_size`` rows in
    an order drawn from (seed, epoch): their first ``seq_len`` tokens,
    and the next token of each as its label."""

    def __init__(self, rows: np.ndarray, batch_size: int, seed: int):
        self.ds, self.batch_size, self.seed = rows, batch_size, seed
        self.num_batches = len(rows) // batch_size

    def batch_at(self, epoch: int, b: int) -> Dict[str, np.ndarray]:
        order = np.random.default_rng((self.seed, epoch)).permutation(
            len(self.ds))
        rows = self.ds[order[b * self.batch_size:(b + 1) * self.batch_size]]
        return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}


class Cell(testbed.Cell):
    def _arch(self):
        """The registry's reduced size, or the configuration's ``sizes``
        where it gives them."""
        from repro.models import registry
        arch = registry.get_config(self.config["arch"])
        sizes = self.config.get("sizes")
        return arch.replace(**sizes) if sizes else registry.make_reduced(
            arch)

    def check_config(self) -> None:
        arch = self._arch()
        for key in ("num_layers", "d_model", "vocab_size"):
            if getattr(arch, key) != self.config[key]:
                raise ValueError(f"configuration {key} {self.config[key]} "
                                 f"is not the program's {getattr(arch, key)}")
        if arch.tie_embeddings:
            raise ValueError("the merge below keeps one embedding; the "
                             "configuration needs untied embeddings")

    def build_model(self):
        from repro.models import registry
        return registry.build_model(self._arch())

    def build_batchers(self) -> List[Any]:
        cfg, tr = self.config, self.traffic
        rows = np.random.default_rng([self.seed, 1]).integers(
            0, cfg["vocab_size"], (tr["samples"], cfg["seq_len"] + 1),
            dtype=np.int32)
        parts = data.split(tr["samples"], tr["fractions"], self.seed)
        return [TokenBatcher(rows[i], cfg["batch_size"], self.seed)
                for i in parts]

    def merge(self, dev, srv):
        from repro.core import split
        return split.merge_params(self.model, dev, srv)
