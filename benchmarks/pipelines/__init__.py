"""Pipelines, one per kind of data, found by the configuration's `pipeline`."""
