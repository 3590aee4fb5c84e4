"""admission_us_per_batch: host time of a finished batch's admission calls
(admit, every keepalive, complete) into launch.serve.BatchAdmission, mean
over the batches of the run (host clock around each call)."""


def read(rec):
    done = [b["admission_s"] for b in rec.get("batches", []) if b["finished"]]
    return sum(done) / len(done) * 1e6 if done else None
