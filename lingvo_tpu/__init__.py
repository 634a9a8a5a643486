"""lingvo_tpu: a TPU-native sequence-model framework."""

import time

# The zero of the process's start-up record (observe.profile.Startup()): what
# lies between this stamp and the first `build` phase is the caller's imports
# and whatever it did before it built an engine or a program.
T_IMPORT = time.perf_counter()
