"""Host time of batching per block of the traced window: the self time
of the program's ``mgk.batch`` spans (``BucketedDataset.batch``: host
padding and the transfer of both pair batches), in ms. Nothing where the
program opens no such span."""
import progtrace


def read(run):
    return progtrace.self_ms_per_block(run, ("mgk.batch",))
