"""Self seconds of every compile event under no named program before the
window (the eager ops of InitPagedDecodeState, state_layout's gather and
scatter, jnp.asarray placements, the harness's own jits). The note
startup_other_programs lists the ten largest by `fun_name`."""
from benchmarks.harness import startup

Read = startup.OtherPrograms
