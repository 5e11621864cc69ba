from repro.data.ingest import (TraceCalibration, TraceParseError,
                               calibrate_generators, generate_calibrated,
                               load_trace, read_csv_trace, read_jobs_info,
                               read_jsonl_trace, read_nodes_info,
                               write_jobs_info, write_nodes_info)
