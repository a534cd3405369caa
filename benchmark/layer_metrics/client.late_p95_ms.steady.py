"""How late the generator fired, 95th percentile: actual fire - due time."""
from harness import percentile


def read(obs):
    late = obs.get("lateness_ms")
    return percentile(sorted(late), 0.95) if late else None
