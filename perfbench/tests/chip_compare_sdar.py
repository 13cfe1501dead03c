"""The comparison that decides ``correct`` in a cell of the ``sdar`` family,
made over many seeds in one process, with its control: what a builder runs
on the chip to set the limits in the configuration file.

    python3 perfbench/tests/chip_compare_sdar.py \
        --config perfbench/configs/sdar-30b-a3b-chat.json \
        --traffic perfbench/traffic/step-bd-4k.json --seeds 1,2,3 [--grad 1] \
        [--control 1] [--load-steps 100] [--out chiprun_out/pr65]

The script is ``chip_compare_qwen3_next.py``'s, which asks nothing of its
family but the worker's contract and ``loss_with_parts`` (its docstring says
what each option prints): the state from the seed as the worker makes it
(the held experts levelled), the float32 reference over the seeded batch
under the first step's noise, one real step and the worker's verdict under
the configuration's limits; with ``--control 1`` the same from weights kept
to 3 bits of mantissa; with ``--load-steps n`` the tokens each held expert
received before each of n steps on the one batch and each step's wall time.
This family's ``loss_with_parts`` draws the FIRST step's noise whatever the
step: the load printed before step n is that of the two streams under that
draw with step n's weights, while the step itself redraws.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.tests.chip_compare_qwen3_next import main  # noqa: E402

if __name__ == "__main__":
    main()
