"""Work counts per model family, from the architecture's shapes:
`work/<family>.py`, found by the configuration's `family`.

A family's work file gives, for a configuration file's dict `config`, a
traffic file's dict `traffic` and `batch` samples on one chip:
  * `model_flops_per_sample(config, traffic)`: the FLOPs of training on one
    sample (forward and backward), for `mfu` and `step_mfu`.  Required:
    `run.py` refuses at set-up a family whose work file lacks it.
  * `conv_work(config, traffic, batch)`: (FLOPs, least HBM bytes) of one
    step's convolutions, for `conv_roofline`;
  * `quantized_elements(config, traffic, batch)`: elements one step puts on
    an int8 grid through the quantize kernel, for `quantize_roofline`.
The last two are optional: a reader returns nothing where the family's work
file has no such count.  A sample is what the traffic's kind makes one of
(`traffic.py`): an image, or a sequence of the traffic's `seq` tokens.
"""
import importlib


def for_config(config):
    return importlib.import_module(f"bench.work.{config['family']}")
