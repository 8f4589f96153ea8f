from repro.baselines.coordinate_descent import elastic_net_cd
from repro.baselines.fista import elastic_net_fista

__all__ = ["elastic_net_cd", "elastic_net_fista"]
