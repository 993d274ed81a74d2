from repro.federated.aggregation import (staleness_alpha, staleness_mix,
                                         weighted_average)
from repro.federated.executors import (ClassicExecutor, LMExecutor,
                                        TokenLMExecutor)
from repro.federated.local_sgd import (ELMeshState, el_state_specs,
                                       init_el_state, make_el_round)
from repro.federated.simulator import ELSimulator, SimResult

__all__ = [
    "weighted_average", "staleness_mix", "staleness_alpha",
    "ClassicExecutor", "LMExecutor", "TokenLMExecutor", "ELSimulator",
    "SimResult",
    "ELMeshState", "init_el_state", "make_el_round", "el_state_specs",
]
