"""What the ``nemotron_twotower`` readers under ``metrics/`` share beside
``ling_readers.py``'s ``scoped_ms`` and ``share``: whether the run's
configuration is of this family at all.  A reader returns None, never 0,
where it is not (a program without the backbone, the parent commit)."""

from __future__ import annotations


def has_ssm(reading) -> bool:
    decoder = reading["config"].get("reference", {}).get("decoder", {})
    return "mamba_num_heads" in decoder
