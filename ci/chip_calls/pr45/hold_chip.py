"""A process that opens the chip, says READY and sleeps: the foreign holder
of the repair's one-chip check (`call2.sh`)."""
import time

import jax

x = jax.numpy.ones((1024, 1024)).block_until_ready()
print("READY", jax.devices(), flush=True)
time.sleep(3600)
