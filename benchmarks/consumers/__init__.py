"""Fixed consumer steps, found by the configuration's `consumer`."""
