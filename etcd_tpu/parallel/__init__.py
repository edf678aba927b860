"""Mesh sharding + ICI collectives for group-sharded consensus state.

The reference scales by adding members (3-9) over HTTP (SURVEY §2 #14);
this layer scales the *co-hosted group* dimension over a TPU slice:
tens of thousands of Raft groups' state lives as ``[G, ...]`` arrays
sharded over a `jax.sharding.Mesh`, with XLA collectives over ICI
doing the only cross-device communication (BASELINE config 5).
"""

from .mesh import (
    check_group_divisible,
    data_plane_step,
    group_mesh,
    make_replay_commit_step,
    make_sharded_step,
    place_step_inputs,
    replay_commit_local,
    serving_mesh,
    shard_leading,
)

__all__ = [
    "data_plane_step",
    "check_group_divisible",
    "group_mesh",
    "make_replay_commit_step",
    "make_sharded_step",
    "place_step_inputs",
    "replay_commit_local",
    "serving_mesh",
    "shard_leading",
]
