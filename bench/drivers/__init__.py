"""Load drivers: each module's ``run(system, mix, seed, seconds, span)``
drives the engine through one measured window and returns a `Window`."""
