"""One module per kind of runner; a configuration's file names its kind.
Each exposes `run(ctx) -> harness.Measurement`."""
