from .pipeline import (PrefetchIterator, assemble_message_batch,
                       batch_from_columns, iter_message_batches, payload_blob,
                       payload_matrix)

__all__ = ["PrefetchIterator", "assemble_message_batch", "batch_from_columns",
           "iter_message_batches", "payload_blob", "payload_matrix"]
