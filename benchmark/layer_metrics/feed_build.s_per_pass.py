"""Layer ``feed_build`` (data/pass_feed.py, trainer.build_pass_feed):
seconds a pass spends packing on the host (``pack_pass_host``, on the
prefetch worker) plus uploading and planning on the device
(``finish_pass_feed``), mean over the window's passes.  The benchmark's
spans, host clock."""


def read(run):
    pack = run.span_seconds("pack_pass_host")
    finish = run.span_seconds("finish_pass_feed")
    if not pack and not finish:
        return None
    return (sum(pack) + sum(finish)) / max(len(pack), len(finish))
