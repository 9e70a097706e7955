"""Architecture modules: everything of the yardstick that depends on a
model's shape.

A configuration file names its module by the key ``bench_arch``:
``"dense_gqa"`` is ``bench/arch/dense_gqa.py``, and a dotted name is a
module path of its own (a test's toy module).  The harness (``run.py``,
``check.py``, the per-layer readers) reaches shape-specific code only
through ``load(config)``, so a new architecture comes as new files: its
module, its configuration file, its cells' limits and readers, and its
entries in ``BENCHMARK.json``.

What a module provides (``cfg`` is the configuration file's dict):

* ``program_fields(cfg)``: the program's ``ModelConfig`` fields set from
  the file (``dataclasses.replace`` of the program's preset);
* ``checked_fields(cfg)``: ``ModelConfig`` attributes that must equal the
  file's numbers, by name;
* ``vocab(cfg)``: the vocabulary's size (the logits compared);
* ``draw_params(cfg, seed, dtype, vocab_rows)``: the weights from the
  seed, in one jitted call on the device (``bench/weights.py`` has the
  seeded drawing every module shares), and ``to_program(params,
  shapes)``, which maps them onto the program's tree and checks each
  shape against the program's;
* ``reference_logits(cfg, seed, seqs, want, length, batch)``: float32
  logits at ``highest`` precision of each sequence at its ``want``
  positions, from the same weights drawn again, importing nothing of
  the program;
* ``step_flops(cfg, spans)``, ``param_count(cfg)`` and
  ``decode_step_bytes(cfg, ctxs)``: the work the algorithm needs,
  computed from shapes;
* ``SMOKE_ARCHS`` and ``file_config(model_config)``: the program presets
  whose smoke sizes ``bench/tests/test_bench_reference.py`` holds the
  reference to the program's forward at, and the file a preset reads as.
"""

from __future__ import annotations

import importlib
from types import ModuleType


def load(config: dict) -> ModuleType:
    """The architecture module that ``config["bench_arch"]`` names."""
    name = config["bench_arch"]
    return importlib.import_module(name if "." in name
                                   else f"{__name__}.{name}")

