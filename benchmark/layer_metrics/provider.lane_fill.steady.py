"""1 - pad slots / lane slots over the window, from the device peer."""
from readers import lane_fill_pct as read  # noqa: F401
