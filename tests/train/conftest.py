import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

# CPU by default (the chip path is opt-in via MXTPU_TRAIN_ON_CHIP=1,
# run from a fresh process on the machine with the device)
if not os.environ.get("MXTPU_TRAIN_ON_CHIP"):
    import jax
    jax.config.update("jax_platforms", "cpu")
