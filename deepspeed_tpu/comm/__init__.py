from .backend import ReduceOp, XlaBackend
from .comm import (CommHandle, all_gather, all_gather_into_tensor, all_reduce, all_to_all, all_to_all_single,
                   barrier, broadcast, coalescing_manager, configure, destroy_process_group, get_local_rank,
                   get_rank, get_world_size, init_distributed, initialize_mesh_device, is_initialized,
                   log_summary, pmax, pmean, ppermute, psum, psum_scatter, reduce_scatter_tensor,
                   ring_send_recv, timed_op)
