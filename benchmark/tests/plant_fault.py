#!/usr/bin/env python
"""Runs a training cell with a FAULT planted in its family's trainer, at the
cell's own size on the chip (or through the tests' rehearsal hook):

    python benchmark/tests/plant_fault.py --fault frozen --workload <cell> \
        --seed <n> --seconds <s>

``run.py`` itself runs the cell; ``correct`` has to come out false, and the
``check`` lines say which limit caught the fault and by how much — the upper
readings a limit is set under (PERF.md, section 2).  The faults:

* ``frozen``: every step runs, and its new parameters and optimizer state
  are thrown away (a step that returns its state unchanged);
* ``half_batch``: the second half of every batch is a copy of the first (a
  step that trains on half of what it was given).
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def frozen(mod):
    import jax

    step = mod.Trainer.step

    def frozen_step(self):
        # the real step runs (it donates its arguments) and its new state is
        # thrown away for the one kept on the HOST: at a cell's own size the
        # chip does not hold a second copy of parameters and optimizer state
        if not hasattr(self, "_frozen"):
            state = (self.p, self.st)
            self._frozen = (jax.device_get(state), jax.tree_util.tree_map(
                lambda a: a.sharding, state))
        loss = step(self)
        self.p = self.st = None
        self.p, self.st = jax.device_put(*self._frozen)
        return loss

    mod.Trainer.step = frozen_step


def half_batch(mod):
    import jax
    import jax.numpy as jnp

    init = mod.Trainer.__init__

    def halved(self, ctx):
        init(self, ctx)
        half = jax.jit(lambda t: jnp.concatenate(
            [t[: t.shape[0] // 2]] * 2), donate_argnums=0)
        self.pool = [tuple(half(t) for t in batch) for batch in self.pool]

    mod.Trainer.__init__ = halved


FAULTS = {"frozen": frozen, "half_batch": half_batch}


def plant(fault: str, family: str):
    """Patch ``loader.module`` so that ``families/<family>.py`` comes with
    ``fault`` planted; returns the function that undoes it."""
    from benchmark.harness import loader

    real = loader.module

    def module(kind, name):
        mod = real(kind, name)
        if (kind, name) == ("families", family):
            FAULTS[fault](mod)
        return mod

    loader.module = module

    def undo():
        loader.module = real
    return undo


def main(argv=None, **hooks) -> int:
    """``hooks``: ``run.main``'s rehearsal hooks (the tests')."""
    from benchmark import run
    from benchmark.harness import loader

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--fault", required=True, choices=sorted(FAULTS))
    parser.add_argument("--workload", required=True)
    args, rest = parser.parse_known_args(argv)
    cell = loader.cell(loader.manifest(), args.workload)
    undo = plant(args.fault, cell["config"]["family"])
    try:
        return run.main(["--workload", args.workload] + rest, **hooks)
    finally:
        undo()


if __name__ == "__main__":
    sys.exit(main())
