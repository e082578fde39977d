from .fleet import (FLEET_AXIS, FleetPointMetrics, FleetStream, fleet_mesh,
                    fleet_shard, fleet_point_metrics, fleet_encode,
                    pad_to_mesh)

__all__ = ["FLEET_AXIS", "FleetPointMetrics", "FleetStream", "fleet_mesh",
           "fleet_shard", "fleet_point_metrics", "fleet_encode",
           "pad_to_mesh"]
