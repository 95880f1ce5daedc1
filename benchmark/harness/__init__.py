"""The benchmark's yardstick: everything here is general and driven by the
data files beside it (configs/, traffic/, cells/, layer_metrics/)."""
