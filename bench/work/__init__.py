"""Work counts per model family, from the architecture's shapes:
`work/<family>.py`, found by the configuration's `family`."""
import importlib


def for_config(config):
    return importlib.import_module(f"bench.work.{config['family']}")
