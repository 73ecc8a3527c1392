"""Share of the traced window spent in the EC parity leg: host spans
around rs_parity.ops.ec_encode and ec_parity_delta, each covering
its device round trip (bulk writes)."""
from bench.readers import parity_leg_share as read  # noqa: F401
