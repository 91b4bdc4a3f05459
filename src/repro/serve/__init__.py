"""Fleet-scale DIVA serving layer: online timing-table queries over a live
DIMM fleet (signature-cache hits, discovery on miss, staleness-driven
re-profiling, checkpointed state)."""
from repro.serve.server import (FleetConfig, FleetServer, concat_batches,
                                oracle_parity, take_batch)
from repro.serve.state import (PATH_CONVENTIONAL, PATH_DISCOVER, PATH_HIT,
                               FleetState, GenerationCache)
