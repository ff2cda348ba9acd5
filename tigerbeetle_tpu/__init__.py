"""tigerbeetle_tpu: a TPU-native financial-transactions database.

A from-scratch framework with the capabilities of the reference
TigerBeetle (surveyed in SURVEY.md): the double-entry accounting state
machine runs as a JAX/XLA kernel against an HBM-resident account table,
surrounded by a host runtime (WAL, consensus, message bus, clients).
"""

from tigerbeetle_tpu import constants, types

__all__ = ["constants", "types"]
__version__ = "0.1.0"
