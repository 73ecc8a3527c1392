"""Build the store a configuration file describes.

A configuration states the fleet (targets, media devices per target,
fault domains), the redundancy class, where the client runs and over
which transport, and the inline services. This turns it into one
`ROS2Client` and refuses to go on when the store's geometry is not the
one the configuration states.
"""
from __future__ import annotations

from typing import Any, Dict


def make_client(cfg: Dict[str, Any]):
    from repro.core.client import ROS2Client

    red = cfg["redundancy"]
    kw: Dict[str, Any] = dict(
        mode=cfg["client_mode"], transport=cfg["transport"],
        n_targets=cfg["targets"], n_devices=cfg["media_devices_per_target"],
        domains=cfg.get("fault_domains"),
        inline_encryption=cfg["inline_encryption"],
        scrub_interval_s=cfg["scrub_interval_s"] if cfg["scrub"] else None)
    if "k" in red:
        kw["ec"] = (red["k"], red["p"])
    else:
        kw["replication"] = red["replicas"]
    client = ROS2Client(**kw)
    try:
        check_geometry(client, cfg)
    except ValueError:
        client.close()
        raise
    return client


def check_geometry(client, cfg: Dict[str, Any]) -> None:
    red = cfg["redundancy"]
    if len(client.cluster.targets) != cfg["targets"]:
        raise ValueError(f"store has {len(client.cluster.targets)} targets, "
                         f"configuration states {cfg['targets']}")
    if "k" in red:
        k, p = red["k"], red["p"]
        want = (k, p, red["stripe_bytes"] // k)
        got = tuple(client.io._ec)
        if got != want:
            raise ValueError(f"store runs ec geometry {got}, configuration "
                             f"states {want}")
    else:
        got = client.ccontainer.target(0).replication
        if got != red["replicas"]:
            raise ValueError(f"store keeps {got} replicas, configuration "
                             f"states {red['replicas']}")
