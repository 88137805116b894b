"""The plain reference's consensus core: a copy, taken at PR 25, of the
repo's scalar raft-rs port (`raft_tpu/{raft,raft_log,storage,...}.py` and
its `tracker/ quorum/ confchange/ harness/` packages), file for file and
unedited.  It lives under the benchmark's path so that no later PR can
change what the device path is compared with.  Pure Python: it imports
neither jax nor anything of `raft_tpu`."""
